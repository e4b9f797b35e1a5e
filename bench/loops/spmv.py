"""Closed-loop SpMV: one caller issues ``SpMV.matvec`` on x vectors drawn
from the seed, x changing from call to call, with ``IN_FLIGHT`` calls
outstanding: it blocks on the oldest before it issues one more.  End to
end: ``spmv_ms``, the window over the matvecs done."""
from __future__ import annotations

import collections
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, work

# The caller is an iterative solver under JAX's asynchronous dispatch
# (as jax.scipy.sparse.linalg.cg): it never reads y on the host, so the
# next matvec is queued before the last one ends.  One queued call is the
# least such a caller keeps; host gaps shorter than a matvec then overlap
# the device's work instead of idling it.
IN_FLIGHT = 2


def build(struct, options: dict):
    from repro.core.apps import SpMV
    return SpMV.from_coo(struct.rows, struct.cols, struct.vals,
                         struct.shape, **options)


def entry(app):
    return app.matvec


@jax.jit
def _make_x(key, i, like):
    return jax.random.normal(jax.random.fold_in(key, i), like.shape,
                             jnp.float32)


class Session:
    def __init__(self, struct, call, traffic: dict, seed: int):
        self.struct, self.call = struct, call
        key, like = jax.random.key(seed), jnp.zeros(struct.shape[1])
        self.pool = int(traffic["x_pool"])
        self.xs = [_make_x(key, i, like) for i in range(self.pool)]
        self.sample = int(traffic["check_sample"])
        self.rng = np.random.default_rng([seed, 1])
        self.pending = collections.deque()   # issued (call index, y)
        self.kept: list = []          # reservoir of (call index, y)
        self.attempted = self.failed = self.completed = 0

    def warm(self):
        for _ in range(2):
            jax.block_until_ready(self.call(self.xs[0]))

    def step(self):
        i = self.attempted
        self.attempted += 1
        try:
            self.pending.append((i, self.call(self.xs[i % self.pool])))
        except Exception:
            self._fail()
        while len(self.pending) >= IN_FLIGHT:
            self._complete(*self.pending.popleft())

    def drain(self):
        while self.pending:
            self._complete(*self.pending.popleft())

    def _fail(self):
        self.failed += 1
        traceback.print_exc(file=sys.stderr)

    def _complete(self, i, y):
        try:
            jax.block_until_ready(y)
        except Exception:
            self._fail()
            return
        # reservoir sample drawn from the seed: every completed call is
        # equally likely to be compared
        c = self.completed
        self.completed += 1
        if len(self.kept) < self.sample:
            self.kept.append((i, y))
        else:
            j = int(self.rng.integers(0, c + 1))
            if j < self.sample:
                self.kept[j] = (i, y)

    def finish(self):
        slots = {i % self.pool for i, _ in self.kept}
        self.x_host = {s: np.asarray(self.xs[s]) for s in slots}
        self.kept = [(i, np.asarray(y)) for i, y in self.kept]
        self.xs = self.call = None

    def metrics(self, elapsed_s: float) -> dict:
        return {"spmv_ms": elapsed_s / self.completed * 1e3}

    def counters(self) -> dict:
        return {}

    def work_bytes(self) -> int:
        m, n = self.struct.shape
        return self.completed * work.spmv_bytes(self.struct.nnz, m, n)

    def check(self, limits: dict) -> dict:
        refs, worst = {}, 0.0 if self.kept else float("inf")
        for i, y in self.kept:
            slot = i % self.pool
            if slot not in refs:
                refs[slot] = reference.spmv_reference(self.struct,
                                                      self.x_host[slot])
            worst = max(worst, reference.spmv_error(y, *refs[slot]))
        return {"spmv_err": (worst, limits["spmv_err"])}
