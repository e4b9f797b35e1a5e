"""Device scopes (DESIGN.md §11): every launch kind of a sweep program
runs under its ``jax.named_scope`` from ``repro.obs.trace``, so the
compiled program's ``op_name`` metadata (on the chip, the device trace's
``tf_op`` stat) names the layer each op belongs to.  The scopes add
metadata and nothing else: with them taken out the compiled program is
the same op for op."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import ir
from repro.core.apps import SpMV
from repro.core.graphs import BFS
from repro.obs import trace
from repro.sparse import generators as G

KIND_SCOPE = {ir.WINDOW: trace.SCOPE_WINDOW, ir.STREAM: trace.SCOPE_WINDOW,
              ir.FALLBACK: trace.SCOPE_FALLBACK,
              ir.COALESCED: trace.SCOPE_COALESCED}


def _spmv(backend, coalesce=False, **kw):
    """A banded matrix (dense-slice launches) under ``coalesce``, else a
    power-law one (window and fallback launches)."""
    m = G.banded(n=300, band=5) if coalesce else \
        G.power_law(n=400, avg_deg=6)
    return SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                         np.asarray(m.vals), m.shape, lane_width=32,
                         backend=backend, coalesce=coalesce, **kw)


def _lowered(app):
    x = jnp.zeros(app.shape[1], jnp.float32)
    return app._run.lower({"x": x}, jnp.zeros(app.shape[0], jnp.float32))


def _scopes_in(text: str) -> set:
    """Vocabulary scopes named by some op's ``op_name`` / location."""
    names = re.findall(r'op_name="([^"]*)"', text) or \
        re.findall(r'loc\("([^"]*)"', text)
    return {part for n in names for part in n.split("/")
            if part in trace.DEVICE_SCOPES}


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("coalesce", [False, True])
def test_each_launch_kind_carries_its_scope(backend, coalesce):
    # the fused XLA form sends every power-law block to the fallback
    # gather, so the jax backend shows the window kind per class
    app = _spmv(backend, coalesce, fused=backend != "jax" or coalesce)
    kinds = {KIND_SCOPE[launch.gather] for launch in app._run.tree.launches}
    if coalesce:
        assert trace.SCOPE_COALESCED in kinds
    else:
        assert {trace.SCOPE_WINDOW, trace.SCOPE_FALLBACK} <= kinds
    want = kinds | {trace.SCOPE_STAGE_B}
    lowered = _lowered(app)
    assert _scopes_in(lowered.as_text(debug_info=True)) == want
    assert _scopes_in(lowered.compile().as_text()) == want


def test_segsum_scopes_its_gather_and_fold():
    hlo = _lowered(_spmv("segsum")).compile().as_text()
    assert _scopes_in(hlo) == {trace.SCOPE_FALLBACK, trace.SCOPE_STAGE_B}


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_resident_loop_scopes_its_fixpoint_check(backend):
    m = G.power_law(n=300, avg_deg=5)
    bfs = BFS.from_edges(np.asarray(m.rows), np.asarray(m.cols), m.shape[0],
                         lane_width=32, backend=backend)
    fn = bfs._resident_converge(False)
    state = jnp.zeros(m.shape[0], jnp.int32)
    hlo = fn.jitted.lower(fn.consts, state, jnp.int32(3)).compile().as_text()
    found = _scopes_in(hlo)
    assert {trace.SCOPE_FIXPOINT_CHECK, trace.SCOPE_STAGE_B} <= found
    assert any(f"/{trace.SCOPE_FIXPOINT_CHECK}/" in n
               for n in re.findall(r'op_name="([^"]*)"', hlo)
               if "/while/body/" in n)


def _normalized(hlo: str) -> list:
    """Compiled HLO without metadata, source tables or names: opcodes,
    shapes, attributes and operand order, op for op."""
    body = hlo[hlo.index("\n%") if "\n%" in hlo else hlo.index("ENTRY"):]
    body = re.sub(r", metadata=\{[^}]*\}", "", body)
    body = re.sub(r"%[\w.\-]+", "%_", body)
    return [ln for ln in body.splitlines() if ln.strip()]


@pytest.mark.parametrize("backend,coalesce", [("jax", False), ("jax", True),
                                              ("segsum", False),
                                              ("pallas", False)])
def test_scopes_add_metadata_only(backend, coalesce, monkeypatch):
    scoped = _normalized(_lowered(_spmv(backend, coalesce)).compile()
                         .as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(eng, "_stage_b", eng._stage_b.__wrapped__)
    monkeypatch.setattr(eng, "_stage_b_dense",
                        eng._stage_b_dense.__wrapped__)
    bare_hlo = _lowered(_spmv(backend, coalesce)).compile().as_text()
    assert not _scopes_in(bare_hlo)
    assert _normalized(bare_hlo) == scoped
