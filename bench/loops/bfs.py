"""Closed-loop BFS, Graph500 kernel 2: one caller runs ``BFS.run(key)`` for
successive search keys, cycling through the mix's set of ``search_keys``
vertices of degree >= 1.  The set is drawn from the mix's
``key_set_seed``, so every run searches the same keys and does the same
work; ``--seed`` draws their order.  End to end: ``bfs_mteps``,
Graph500's traversed edges (input tuples inside each search's component,
self-loops and duplicates included) summed over every search done, over
the time from the window's start to the last completion."""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from bench import reference, work


def build(struct, options: dict):
    from repro.core.graphs import BFS
    return BFS.from_edges(struct.cols, struct.rows, struct.shape[0],
                          **options)


def entry(app):
    def run(root, max_sweeps=None):
        levels = app.run(root, max_sweeps=max_sweeps)
        return levels, app.convergence.sweeps, app.convergence.converged
    return run


class Session:
    def __init__(self, struct, call, traffic: dict, seed: int):
        self.struct, self.call = struct, call
        candidates = np.flatnonzero(struct.degree > 0)
        keys = np.random.default_rng(int(traffic["key_set_seed"])).choice(
            candidates, size=min(int(traffic["search_keys"]),
                                 candidates.size), replace=False)
        self.keys = np.random.default_rng(seed).permutation(keys)
        self.sample = int(traffic["check_sample"])
        self.seed = seed
        self.done: list = []    # (key, levels, sweeps, converged)
        self.seconds: list = []  # host seconds of each completed search
        self.attempted = self.failed = 0

    @property
    def completed(self) -> int:
        return len(self.done)

    def warm(self):
        # one sweep compiles and runs the same program a search runs
        self.call(int(self.keys[0]), max_sweeps=1)

    def step(self):
        key = int(self.keys[self.attempted % self.keys.size])
        self.attempted += 1
        t = time.perf_counter()
        try:
            levels, sweeps, converged = self.call(key)
        except Exception:
            self.failed += 1
            traceback.print_exc(file=sys.stderr)
            return
        self.seconds.append(time.perf_counter() - t)
        self.done.append((key, np.asarray(levels), int(sweeps),
                          bool(converged)))

    def drain(self):
        pass              # each search is blocked on in step()

    def finish(self):
        self.call = None
        print("[bench] searches: sweeps="
              f"{[s for _, _, s, _ in self.done]} seconds="
              f"{[round(s, 4) for s in self.seconds]}", file=sys.stderr)

    def _reached(self):
        return [levels >= 0 for _, levels, _, _ in self.done]

    def metrics(self, elapsed_s: float) -> dict:
        u = self.struct.tuples[:, 0]
        edges = sum(int(np.count_nonzero(r[u])) for r in self._reached())
        return {"bfs_mteps": edges / elapsed_s / 1e6}

    def counters(self) -> dict:
        if not self.done:
            return {}
        return {"bfs_sweeps": float(np.mean([s for _, _, s, _ in self.done]))}

    def work_bytes(self) -> int:
        deg, n = self.struct.degree, self.struct.shape[0]
        return sum(work.bfs_bytes(int(deg[r].sum()), n)
                   for r in self._reached())

    def check(self, limits: dict) -> dict:
        rng = np.random.default_rng([self.seed, 2])
        picked = rng.permutation(len(self.done))[:self.sample]
        indptr, cols = self.struct.indptr, self.struct.cols
        differing = 0 if self.done else self.struct.shape[0]
        refs = {}
        for k in picked:
            key, levels, _, _ = self.done[k]
            if key not in refs:
                refs[key] = reference.bfs_reference(indptr, cols, key)
            differing += int(np.count_nonzero(levels != refs[key]))
        unconverged = sum(not c for _, _, _, c in self.done)
        return {"levels_differing": (differing, limits["levels_differing"]),
                "unconverged": (unconverged, limits["unconverged"])}
