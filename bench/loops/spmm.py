"""Closed-loop SpMM: one caller issues ``SpMM.matmat`` on ``H`` matrices
of the mix's ``width`` columns drawn from the seed, ``H`` changing from
call to call, with ``IN_FLIGHT`` calls outstanding: it blocks on the
oldest before it issues one more.  End to end: ``spmv_ms``, the window
over the products done.

A product's ``Y`` is as large as ``H``, so the check keeps, of each
sampled product, only the rows it compares, sliced on the device: rows
drawn from the seed and the rows of highest degree."""
from __future__ import annotations

import collections
import functools
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, reference_spmm, work_spmm

# The caller is a GCN forward pass under JAX's asynchronous dispatch,
# which never reads Y on the host: the next layer's product is queued
# before the last one ends, as in loops/spmv.py.
IN_FLIGHT = 2


def build(struct, options: dict):
    from repro.core.spmm import SpMM
    return SpMM.from_coo(struct.rows, struct.cols, struct.vals,
                         struct.shape, **options)


def entry(app):
    return app.matmat


@functools.partial(jax.jit, static_argnums=(2,))
def _make_h(key, i, shape):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


@jax.jit
def _rows_of(y, rows):
    return y[rows]


class Session:
    def __init__(self, struct, call, traffic: dict, seed: int):
        self.struct, self.call = struct, call
        self.width = int(traffic["width"])
        key = jax.random.key(seed)
        self.pool = int(traffic["h_pool"])
        self.hs = [_make_h(key, i, (struct.shape[1], self.width))
                   for i in range(self.pool)]
        self.rows = reference_spmm.sample_rows(
            struct, np.random.default_rng([seed, 2]),
            int(traffic["check_rows"]), int(traffic["check_top_rows"]))
        self.rows_dev = jnp.asarray(self.rows, jnp.int32)
        self.sample = int(traffic["check_sample"])
        self.rng = np.random.default_rng([seed, 1])
        self.pending = collections.deque()   # issued (call index, Y)
        self.kept: list = []        # reservoir of (call index, Y's rows)
        self.attempted = self.failed = self.completed = 0

    def warm(self):
        # one product compiles every shape: a second would add a whole
        # product's seconds to the set-up and warm nothing more
        jax.block_until_ready(_rows_of(self.call(self.hs[0]),
                                       self.rows_dev))

    def step(self):
        i = self.attempted
        self.attempted += 1
        try:
            self.pending.append((i, self.call(self.hs[i % self.pool])))
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
        j = c if c < self.sample else int(self.rng.integers(0, c + 1))
        if j < self.sample:
            kept = (i, _rows_of(y, self.rows_dev))
            if j < len(self.kept):
                self.kept[j] = kept
            else:
                self.kept.append(kept)

    def finish(self):
        slots = sorted({i % self.pool for i, _ in self.kept})
        self.cols = reference_spmm.needed_cols(self.struct, self.rows)
        cols = jnp.asarray(self.cols, jnp.int32)
        self.h_host = {s: np.asarray(jnp.take(self.hs[s], cols, axis=0))
                       for s in slots}
        self.kept = [(i, np.asarray(y)) for i, y in self.kept]
        self.hs = self.call = None

    def metrics(self, elapsed_s: float) -> dict:
        return {"spmv_ms": elapsed_s / self.completed * 1e3}

    def counters(self) -> dict:
        return {}

    def work_bytes(self) -> int:
        m, n = self.struct.shape
        return self.completed * work_spmm.spmm_bytes(self.struct.nnz, m, n,
                                                     self.width)

    def check(self, limits: dict) -> dict:
        refs, worst = {}, 0.0 if self.kept else float("inf")
        for i, y in self.kept:
            slot = i % self.pool
            if slot not in refs:
                refs[slot] = reference_spmm.spmm_reference(
                    self.struct, self.rows, self.cols, self.h_host[slot])
            worst = max(worst, reference.spmv_error(y, *refs[slot]))
        return {"spmv_err": (worst, limits["spmv_err"])}
