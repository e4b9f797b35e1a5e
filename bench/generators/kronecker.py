"""Graph500 Kronecker graph (Graph500 specification, section 3.4).

``M = edgefactor * 2**scale`` edge tuples; each tuple picks one quadrant
per bit with probabilities ``A, B, C, D``; vertex labels are then
permuted at random.  The quadrant bits are drawn on the device in one
jitted call from the configuration's ``generator_seed``; the label
permutation and the sort are made on the host, where they cost a few
seconds and no compile (a sort on the TPU takes tens of seconds to
compile).  The structure is the deployment: a run's ``--seed`` draws
only the search keys and vectors that run uses.  The graph is made
undirected as Graph500 kernel 1 does: every tuple in both directions (a
self-loop once), sorted row-major, duplicates kept.

Matrix values, for SpMV on the adjacency: ``1 / degree(col)``, the
column-stochastic transition of a PageRank power iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.structure import Structure


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _tuples(key, scale: int, m: int, probs: tuple):
    a, b, c = probs
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)

    def bit(ib, uv):
        u, v = uv
        k1, k2 = jax.random.split(jax.random.fold_in(key, ib))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (u | (ii.astype(jnp.int32) << ib),
                v | (jj.astype(jnp.int32) << ib))

    zeros = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, bit, (zeros, zeros))


def make(cfg: dict) -> Structure:
    scale, n = int(cfg["scale"]), 1 << int(cfg["scale"])
    m = int(cfg["edgefactor"]) * n
    probs = (float(cfg["A"]), float(cfg["B"]), float(cfg["C"]))
    seed = int(cfg["generator_seed"])
    u, v = jax.device_get(_tuples(jax.random.key(seed), scale, m, probs))
    perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
    u, v = perm[u], perm[v]
    # both directions as one int64 key per entry, row in the high word; a
    # self-loop's mirror is dropped
    fwd = (u.astype(np.int64) << 32) | v
    back = (v.astype(np.int64) << 32) | u
    keys = np.concatenate([fwd, back[u != v]])
    keys.sort()
    rows, cols = (keys >> 32).astype(np.int32), keys.astype(np.int32)
    del keys
    deg = np.bincount(rows, minlength=n)
    inv = (1.0 / np.maximum(deg, 1)).astype(np.float32)
    return Structure(rows=rows, cols=cols, vals=inv[cols], shape=(n, n),
                     tuples=np.stack([u, v], axis=1))
