"""Robustness-model tests (DESIGN.md §9).

Every claim in the robustness model is driven end-to-end here:
validation policies against scipy/dict oracles, each graceful-degradation
rung under injected faults (:mod:`repro.testing.faults`), fixpoint health
(divergence + negative-cycle detection) on both drivers, and degenerate
inputs.  The invariant throughout: a degraded build still produces the
bitwise-correct result, leaves a structured DegradationEvent trail, and
never lets an exception escape the constructor.
"""
import json
import os
import subprocess
import sys
import threading
import warnings

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from repro.core import validate as V
from repro.core.apps import PageRank, SpMV, pagerank_reference
from repro.core.graphs import BFS, SSSP, ConnectedComponents
from repro.core.spmm import SpMM
from repro.testing import faults

pytestmark = pytest.mark.robust


def _coo(rng, m, n, nnz, dup_frac=0.0):
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, n, nnz)
    if dup_frac:
        k = int(nnz * dup_frac)
        rows[:k] = rows[nnz - k:]
        cols[:k] = cols[nnz - k:]
    vals = rng.standard_normal(nnz).astype(np.float32)
    return rows, cols, vals


def _dict_combine(rows, cols, vals, reduce):
    """Order-independent dedup oracle (dict of coordinate -> combined)."""
    op = {"add": lambda a, b: a + b, "mul": lambda a, b: a * b,
          "min": min, "max": max}[reduce]
    out = {}
    for r, c, v in zip(rows.tolist(), cols.tolist(), vals.tolist()):
        out[(r, c)] = op(out[(r, c)], v) if (r, c) in out else v
    return out


# ---------------------------------------------------------------- strict
class TestStrict:
    def test_out_of_range_row_names_first_offender(self):
        rows = np.array([0, 1, 9, 2, 9])
        cols = np.array([0, 1, 2, 3, 0])
        vals = np.ones(5, np.float32)
        with pytest.raises(V.InputError) as ei:
            V.validate_coo(rows, cols, vals, (4, 4))
        e = ei.value
        assert e.field == "row"
        assert e.count == 2
        assert e.indices[0] == 2           # first offending position
        assert "row[2] = 9" in str(e) and "[0, 4)" in str(e)

    def test_out_of_range_col_negative(self):
        with pytest.raises(V.InputError) as ei:
            V.validate_coo(np.array([0]), np.array([-1]),
                           np.ones(1, np.float32), (4, 4))
        assert ei.value.field == "col"
        assert "col[0] = -1" in str(ei.value)

    def test_nan_payload_rejected(self):
        vals = np.array([1.0, np.nan, 2.0], np.float32)
        with pytest.raises(V.InputError) as ei:
            V.validate_coo(np.array([0, 1, 2]), np.array([0, 1, 2]),
                           vals, (3, 3))
        e = ei.value
        assert e.field == "vals" and e.count == 1 and e.indices[0] == 1

    def test_inf_payload_rejected(self):
        with pytest.raises(V.InputError):
            V.validate_coo(np.array([0]), np.array([0]),
                           np.array([np.inf], np.float32), (3, 3))

    def test_duplicates_are_legal_strict(self):
        rows = np.array([1, 1]); cols = np.array([2, 2])
        r, c, v, rep = V.validate_coo(rows, cols,
                                      np.ones(2, np.float32), (3, 3))
        assert rep.clean and rep.nnz_out == 2
        np.testing.assert_array_equal(r, rows)

    def test_length_mismatch(self):
        with pytest.raises(V.InputError):
            V.validate_coo(np.array([0, 1]), np.array([0]),
                           np.ones(1, np.float32), (3, 3))

    def test_noninteger_index_dtype(self):
        with pytest.raises(V.InputError):
            V.validate_coo(np.array([0.5]), np.array([0]),
                           np.ones(1, np.float32), (3, 3))

    def test_edges_strict_names_offender(self):
        with pytest.raises(V.InputError) as ei:
            V.validate_edges(np.array([0, 7]), np.array([1, 1]), 4)
        assert ei.value.field == "src" and "src[1] = 7" in str(ei.value)

    def test_edges_nonfinite_weight_rejected(self):
        with pytest.raises(V.InputError) as ei:
            V.validate_edges(np.array([0]), np.array([1]), 4,
                             weight=np.array([np.nan], np.float32))
        assert ei.value.field == "weight"

    def test_edges_negative_weight_legal(self):
        _, _, w, rep = V.validate_edges(
            np.array([0]), np.array([1]), 4,
            weight=np.array([-5.0], np.float32))
        assert rep.clean and w[0] == -5.0

    def test_scalar_vals_structured_error(self):
        # a 0-d payload must be a structured InputError, not a bare
        # IndexError out of vals.shape[0]
        for policy in ("strict", "repair"):
            with pytest.raises(V.InputError, match="0-d scalar") as ei:
                V.validate_coo([0], [1], 3.0, (2, 2), policy=policy)
            assert ei.value.field == "vals"
        with pytest.raises(V.InputError, match="0-d scalar") as ei:
            V.validate_csr([0, 1], [0], 3.0, (1, 2))
        assert ei.value.field == "vals"

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown validation policy"):
            V.validate_coo(np.array([0]), np.array([0]),
                           np.ones(1, np.float32), (1, 1), policy="maybe")


# ---------------------------------------------------------------- repair
class TestRepair:
    def test_add_dedup_bitwise_matches_scipy(self):
        rng = np.random.default_rng(7)
        rows, cols, vals = _coo(rng, 50, 40, 600, dup_frac=0.5)
        r, c, v, rep = V.validate_coo(rows, cols, vals, (50, 40),
                                      policy="repair")
        oracle = sp.coo_matrix((vals.copy(), (rows.copy(), cols.copy())),
                               shape=(50, 40))
        oracle.sum_duplicates()
        np.testing.assert_array_equal(r, oracle.row)
        np.testing.assert_array_equal(c, oracle.col)
        # bitwise: same lexsort, same np.add.reduceat as scipy
        assert np.array_equal(v, oracle.data)
        assert rep.duplicates_combined == 600 - oracle.nnz
        assert rep.nnz_out == oracle.nnz and rep.canonicalized

    @pytest.mark.parametrize("reduce", ["add", "min", "max", "mul"])
    def test_semiring_dedup_matches_dict_oracle(self, reduce):
        rng = np.random.default_rng(11)
        rows, cols, vals = _coo(rng, 20, 20, 300, dup_frac=0.6)
        r, c, v, rep = V.validate_coo(rows, cols, vals, (20, 20),
                                      policy="repair", reduce=reduce)
        want = _dict_combine(rows, cols, vals, reduce)
        assert rep.nnz_out == len(want)
        got = {(int(a), int(b)): float(x) for a, b, x in zip(r, c, v)}
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-6)

    def test_drops_out_of_range_and_nonfinite(self):
        rows = np.array([0, 5, 1, 2])
        cols = np.array([0, 1, 9, 2])
        vals = np.array([1.0, 2.0, 3.0, np.nan], np.float32)
        r, c, v, rep = V.validate_coo(rows, cols, vals, (4, 4),
                                      policy="repair")
        assert rep.out_of_range_dropped == 2     # row 5, col 9
        assert rep.nonfinite_dropped == 1        # the NaN
        assert rep.nnz_out == 1 and not rep.clean
        assert (r[0], c[0], v[0]) == (0, 0, 1.0)

    def test_empty_matrix_canonicalized(self):
        r, c, v, rep = V.validate_coo([], [], np.zeros(0, np.float32),
                                      (4, 4), policy="repair")
        assert r.dtype == np.int64 and c.dtype == np.int64
        assert r.size == 0 and rep.canonicalized

    def test_integral_float_indices_cast(self):
        r, c, v, rep = V.validate_coo(np.array([1.0, 2.0]),
                                      np.array([0.0, 3.0]),
                                      np.ones(2, np.float32), (4, 4),
                                      policy="repair")
        assert r.dtype == np.int64
        np.testing.assert_array_equal(r, [1, 2])

    def test_off_is_passthrough(self):
        rows = np.array([99])                    # out of range, untouched
        r, c, v, rep = V.validate_coo(rows, np.array([0]),
                                      np.ones(1, np.float32), (4, 4),
                                      policy="off")
        assert r[0] == 99 and rep.policy == "off"

    def test_edges_repair_drops_bad_keeps_multi(self):
        src = np.array([0, 0, 9, 1])
        dst = np.array([1, 1, 2, 3])
        w = np.array([1.0, 1.0, 1.0, np.inf], np.float32)
        s, d, wr, rep = V.validate_edges(src, dst, 4, weight=w,
                                        policy="repair")
        assert rep.out_of_range_dropped == 1 and rep.nonfinite_dropped == 1
        # duplicate edge 0->1 survives twice: multi-edges are legal
        assert list(s) == [0, 0] and list(d) == [1, 1]


# ------------------------------------------------------------------- csr
class TestCSR:
    def _csr(self, n=16, nnz=60, seed=3):
        rng = np.random.default_rng(seed)
        rows, cols, vals = _coo(rng, n, n, nnz)
        S = sp.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
        return S

    def test_nonmonotone_indptr_raises_every_policy(self):
        for policy in ("strict", "repair"):
            with pytest.raises(V.InputError) as ei:
                V.validate_csr(np.array([0, 5, 3, 10]), np.arange(10),
                               np.ones(10, np.float32), (3, 16),
                               policy=policy)
            assert ei.value.field == "indptr"
            assert "not monotone" in str(ei.value)

    def test_wrong_length_indptr(self):
        with pytest.raises(V.InputError, match="num_rows"):
            V.validate_csr(np.array([0, 2]), np.arange(2),
                           np.ones(2, np.float32), (3, 4))

    def test_indptr_tail_mismatch(self):
        with pytest.raises(V.InputError, match="disagrees"):
            V.validate_csr(np.array([0, 1, 5]), np.arange(2),
                           np.ones(2, np.float32), (2, 4))

    def test_from_csr_rejects_garbage_indptr(self):
        # regression: np.repeat on a non-monotone indptr used to produce
        # silently-garbage rows; now a structured error under any policy
        S = self._csr()
        bad = S.indptr.copy()
        bad[3], bad[4] = bad[4] + 2, bad[3]
        for policy in ("strict", "repair"):
            with pytest.raises(V.InputError):
                SpMV.from_csr(bad, S.indices, S.data, S.shape,
                              validate=policy)

    def test_csr_repair_rebuilds_indptr(self):
        S = self._csr()
        indices = S.indices.copy()
        indices[0] = 999                         # out-of-range column
        indptr, idx, vals, rep = V.validate_csr(
            S.indptr, indices, S.data, S.shape, policy="repair")
        assert rep.out_of_range_dropped == 1
        assert indptr[-1] == len(idx) == len(vals) == S.nnz - 1
        assert np.all(np.diff(indptr) >= 0)

    def test_from_csr_matches_oracle(self):
        S = self._csr()
        A = SpMV.from_csr(S.indptr, S.indices, S.data, S.shape)
        x = np.random.default_rng(0).standard_normal(
            S.shape[1]).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matvec(jnp.asarray(x))),
                                   S @ x, rtol=1e-4, atol=1e-5)
        assert A.validation.policy == "strict"


# ----------------------------------------------------------- end-to-end
class TestEndToEnd:
    def test_spmv_repair_matches_scipy_cleaned(self):
        rng = np.random.default_rng(5)
        n = 40
        rows, cols, vals = _coo(rng, n, n, 400, dup_frac=0.4)
        # poison: a few out-of-range + one NaN
        rows[0] = n + 3
        vals[1] = np.nan
        A = SpMV.from_coo(rows, cols, vals, (n, n), validate="repair")
        assert not A.validation.clean
        keep = (rows < n) & np.isfinite(vals)
        S = sp.coo_matrix((vals[keep], (rows[keep], cols[keep])),
                          shape=(n, n)).tocsr()
        x = rng.standard_normal(n).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matvec(jnp.asarray(x))),
                                   S @ x, rtol=1e-4, atol=1e-5)

    def test_spmv_strict_raises_through_constructor(self):
        with pytest.raises(V.InputError):
            SpMV.from_coo(np.array([9]), np.array([0]),
                          np.ones(1, np.float32), (4, 4))

    @pytest.mark.parametrize("reduce", ["add", "min", "max", "mul"])
    def test_spmm_duplicate_heavy_all_semirings(self, reduce):
        rng = np.random.default_rng(13)
        n = 24
        rows, cols, vals = _coo(rng, n, n, 400, dup_frac=0.7)
        M = SpMM.from_coo(rows, cols, vals, (n, n), reduce=reduce,
                          validate="repair")
        assert M.validation.duplicates_combined > 0
        combined = _dict_combine(rows, cols, vals, reduce)
        from repro.core.seed import reduce_identity_for
        ident = reduce_identity_for(reduce, np.float32)
        B = rng.standard_normal((n, 4)).astype(np.float32)
        got = np.asarray(M.matmat(jnp.asarray(B)))
        npop = {"add": np.add, "min": np.minimum, "max": np.maximum,
                "mul": np.multiply}[reduce]
        # reduce over the ACTUAL (deduped) entries only — absent entries
        # contribute nothing, not identity * B
        want = np.full((n, 4), ident, np.float32)
        for (r, c), v in combined.items():
            want[r] = npop(want[r], np.float32(v) * B[c])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_pagerank_repair_drops_bad_edges(self):
        rng = np.random.default_rng(2)
        n = 30
        src = rng.integers(0, n, 100)
        dst = rng.integers(0, n, 100)
        bad_src = src.copy(); bad_src[0] = n + 7
        pr = PageRank.from_edges(bad_src, dst, n, validate="repair")
        assert pr.validation.out_of_range_dropped == 1
        ref = pagerank_reference(src[1:], dst[1:], n, iters=10)
        np.testing.assert_allclose(np.asarray(pr.run(iters=10)), ref,
                                   atol=1e-5)


# ------------------------------------------------------------ degenerate
class TestDegenerate:
    def test_empty_matrix_spmv(self):
        e = np.zeros(0, np.int64)
        A = SpMV.from_coo(e, e, np.zeros(0, np.float32), (8, 8),
                          validate="repair")
        assert A.validation.nnz_out == 0
        np.testing.assert_array_equal(
            np.asarray(A.matvec(jnp.ones(8, jnp.float32))), np.zeros(8))

    def test_all_dangling_pagerank(self):
        e = np.zeros(0, np.int64)
        pr = PageRank.from_edges(e, e, 5)
        ref = pagerank_reference(e, e, 5, iters=8)
        np.testing.assert_allclose(np.asarray(pr.run(iters=8)), ref,
                                   atol=1e-6)
        # every node dangling -> uniform stationary distribution
        np.testing.assert_allclose(ref, np.full(5, 0.2), atol=1e-6)

    def test_single_node_graph(self):
        one = np.array([0])
        b = BFS.from_edges(one, one, 1)          # self-loop
        np.testing.assert_array_equal(b.run(0), [0])
        assert b.convergence.converged
        cc = ConnectedComponents.from_edges(np.zeros(0, np.int64),
                                            np.zeros(0, np.int64), 1)
        np.testing.assert_array_equal(cc.run(), [0])
        assert cc.convergence.converged and not cc.convergence.diverged


# ------------------------------------------------------- cache degradation
def _small_spmv(tmp_path=None, **kw):
    rng = np.random.default_rng(0)
    rows, cols, vals = _coo(rng, 48, 48, 256)
    x = rng.standard_normal(48).astype(np.float32)
    A = SpMV.from_coo(rows, cols, vals, (48, 48), **kw)
    return A, np.asarray(A.matvec(jnp.asarray(x)))


def _small_spmv_dense():
    """Float64 dense product of :func:`_small_spmv`'s matrix and vector."""
    rng = np.random.default_rng(0)
    rows, cols, vals = _coo(rng, 48, 48, 256)
    x = rng.standard_normal(48).astype(np.float32)
    dense = np.zeros((48, 48))
    np.add.at(dense, (rows, cols), vals.astype(np.float64))
    return dense @ x.astype(np.float64)


class TestDegradationTrail:
    def test_nested_empty_collector_pops_itself_only(self):
        # regression: sinks were removed by equality, so an inner
        # collector that recorded nothing popped the (equal, empty)
        # OUTER sink and the outer exit raised ValueError
        with V.collect_degradations() as outer:
            with V.collect_degradations() as inner:
                pass
            V.record_degradation("tune", "candidate_failed", "d", "f")
        assert len(outer) == 1 and inner == []

    def test_outer_collector_survives_clean_app_builds(self):
        # every constructor opens its own (possibly empty) collector;
        # wrapping two clean builds must not corrupt the caller's sink
        with V.collect_degradations() as trail:
            _small_spmv()
            _small_spmv()
        assert trail == []

    def test_outer_collector_sees_nested_app_events(self, tmp_path):
        V.reset_warn_once()
        cache = tmp_path / "plans"
        with V.collect_degradations() as trail:
            with faults.deny_writes(cache):
                with pytest.warns(RuntimeWarning):
                    A, _ = _small_spmv(plan_cache_dir=str(cache))
        assert any(e.kind == "write_failed" for e in trail)
        assert set(A.degradations) <= set(trail)


def test_fs_faults_scoped_to_injecting_thread(tmp_path):
    # the monkeypatches are process-global; the fault must hit only the
    # thread that entered the context, or concurrent writers (JAX's
    # compilation cache, parallel runners) absorb injected faults
    root = tmp_path / "cache"
    os.makedirs(root)
    got = {}

    def other_thread():
        try:
            with open(root / "other.txt", "w") as f:
                f.write("ok")
            got["result"] = "ok"
        except OSError as e:            # pragma: no cover - failure path
            got["result"] = e

    with faults.deny_writes(root):
        with pytest.raises(OSError):
            open(root / "mine.txt", "w")
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert got["result"] == "ok"
    assert (root / "other.txt").read_text() == "ok"


class TestCacheDegradation:
    def test_readonly_plan_cache_degrades_with_event(self, tmp_path):
        V.reset_warn_once()
        cache = tmp_path / "plans"
        _, y_ref = _small_spmv()                 # no cache: reference
        with faults.deny_writes(cache):
            with pytest.warns(RuntimeWarning, match="plan cache dir"):
                A, y = _small_spmv(plan_cache_dir=str(cache))
        assert np.array_equal(y, y_ref)          # bitwise-equal output
        kinds = {(e.layer, e.kind) for e in A.degradations}
        assert ("plan_cache", "write_failed") in kinds
        assert not os.path.exists(cache)         # nothing was persisted

    def test_readonly_warns_once_per_dir(self, tmp_path):
        V.reset_warn_once()
        cache = tmp_path / "plans"
        with faults.deny_writes(cache):
            with pytest.warns(RuntimeWarning):
                _small_spmv(plan_cache_dir=str(cache))
            with warnings.catch_warnings():      # second build: silent
                warnings.simplefilter("error")
                A, _ = _small_spmv(plan_cache_dir=str(cache))
        # ... but the DegradationEvent trail is still recorded
        assert any(e.kind == "write_failed" for e in A.degradations)

    def test_disk_full_tune_cache_degrades(self, tmp_path):
        V.reset_warn_once()
        cache = tmp_path / "tune"
        os.makedirs(cache)
        with faults.disk_full(cache):
            with pytest.warns(RuntimeWarning, match="tuning cache dir"):
                A, y = _small_spmv(backend="auto",
                                   tune_cache_dir=str(cache))
        assert A.tuning is not None and not A.tuning.cache_hit
        kinds = {(e.layer, e.kind) for e in A.degradations}
        assert ("tune_cache", "write_failed") in kinds
        assert list(cache.iterdir()) == []       # no entry, no leftover tmp

    def test_torn_plan_cache_entry_rebuilds(self, tmp_path):
        V.reset_warn_once()
        cache = tmp_path / "plans"
        with faults.torn_writes(cache):
            _, y1 = _small_spmv(plan_cache_dir=str(cache))
        files = list(cache.glob("*.plan"))
        assert len(files) == 1                   # torn entry was published
        with pytest.warns(RuntimeWarning, match="unreadable"):
            A, y2 = _small_spmv(plan_cache_dir=str(cache))
        assert np.array_equal(y1, y2)
        assert any(e.layer == "plan_cache" and e.kind == "corrupt_entry"
                   for e in A.degradations)
        # the rebuild republished a GOOD entry: third build is silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            _small_spmv(plan_cache_dir=str(cache))

    def test_corrupt_tune_cache_entry_retunes(self, tmp_path):
        cache = tmp_path / "tune"
        A, y1 = _small_spmv(backend="auto", tune_cache_dir=str(cache))
        entries = list(cache.glob("tune-*.json"))
        assert len(entries) == 1
        entries[0].write_text("{not json")
        with pytest.warns(RuntimeWarning, match="unreadable"):
            B, y2 = _small_spmv(backend="auto", tune_cache_dir=str(cache))
        # the re-tune may pick another candidate (timing decides), whose
        # bits may differ; each is held to the exact product
        np.testing.assert_allclose(y2, _small_spmv_dense(),
                                   rtol=1e-5, atol=1e-5)
        if B.tuning.best == A.tuning.best:
            assert np.array_equal(y1, y2)
        assert not B.tuning.cache_hit            # re-tuned for real
        assert any(e.layer == "tune_cache" and e.kind == "corrupt_entry"
                   for e in B.degradations)
        # and republished: a third build is a clean cache hit
        C, _ = _small_spmv(backend="auto", tune_cache_dir=str(cache))
        assert C.tuning.cache_hit

    def test_wrong_schema_tune_entry_retunes(self, tmp_path):
        cache = tmp_path / "tune"
        A, _ = _small_spmv(backend="auto", tune_cache_dir=str(cache))
        entry = list(cache.glob("tune-*.json"))[0]
        entry.write_text(json.dumps({"schema": "tune.v999"}))
        with pytest.warns(RuntimeWarning):
            B, _ = _small_spmv(backend="auto", tune_cache_dir=str(cache))
        assert not B.tuning.cache_hit


# -------------------------------------------------------- tuner degradation
class TestTunerDegradation:
    def test_raising_candidate_disqualified(self):
        with faults.backend_failure("segsum"):
            with pytest.warns(RuntimeWarning, match="disqualified"):
                A, y = _small_spmv(backend="auto")
        assert A.tuning.best.backend != "segsum"
        failed = [m for m in A.tuning.measurements if m.error is not None]
        assert failed and all(m.candidate.backend == "segsum"
                              for m in failed)
        assert any(e.layer == "tune" and e.kind == "candidate_failed"
                   for e in A.degradations)
        _, y_ref = _small_spmv()                 # plain build agrees
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)

    def test_measurement_failure_falls_back_to_cost_model(self, tmp_path):
        cache = tmp_path / "tune"
        with faults.measurement_failure():
            with pytest.warns(RuntimeWarning, match="cost-model"):
                A, y = _small_spmv(backend="auto",
                                   tune_cache_dir=str(cache))
        assert A.tuning.picked_by == "cost_model"
        assert A.tuning.best_us is None
        assert any(e.kind == "measurement_failed"
                   for e in A.degradations)
        # a degraded pick is never cached: next process measures for real
        assert list(cache.glob("tune-*.json")) == []
        _, y_ref = _small_spmv()
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)

    def test_reference_failure_remeasures_with_live_ref(self):
        # measure_paired scales every time by the REFERENCE's rounds; a
        # reference that fails mid-measurement collapses t_ref to noise
        # and poisons every estimate — the harness must discard the
        # paired estimate and re-measure the survivors
        from repro.tune import search
        oi = np.zeros(4, np.float32)

        def good(mutable, o):
            return o

        def bad(mutable, o):
            raise RuntimeError("injected flaky reference")

        timed_fail = {}
        timed = [search._guarded(0, bad, timed_fail),
                 search._guarded(1, good, timed_fail),
                 search._guarded(2, good, timed_fail)]
        with V.collect_degradations() as trail:
            with pytest.warns(RuntimeWarning, match="re-measuring"):
                times = search._paired_times_live_ref(
                    timed, timed_fail, ["ref", "a", "b"], {}, oi, 1, 2)
        assert list(timed_fail) == [0]
        assert times[0] == float("inf")
        assert np.isfinite(times[1]) and np.isfinite(times[2])
        assert any(e.kind == "measurement_failed"
                   and "live reference" in e.fallback for e in trail)

        # every candidate failing leaves all-inf times (the caller then
        # raises its canonical every-candidate-failed error)
        timed_fail = {}
        timed = [search._guarded(i, bad, timed_fail) for i in range(2)]
        with pytest.warns(RuntimeWarning, match="re-measuring"):
            times = search._paired_times_live_ref(
                timed, timed_fail, ["x", "y"], {}, oi, 1, 2)
        assert times == [float("inf")] * 2

    def test_flaky_reference_candidate_end_to_end(self):
        from repro import tune as T
        from repro.core.seed import spmv_seed
        rng = np.random.default_rng(3)
        rows, cols, vals = _coo(rng, 48, 48, 256)
        x = jnp.asarray(rng.standard_normal(48).astype(np.float32))
        state = {"n": 0}

        def wrap(run):
            # measure_wrap is applied in ranked order, so the first
            # wrapped candidate is exactly the paired reference
            i = state["n"]
            state["n"] += 1
            if i == 0:
                def flaky(mutable, oi):
                    raise RuntimeError("injected flaky device queue")
                return flaky
            return run

        with pytest.warns(RuntimeWarning, match="re-measuring"):
            _, _, result = T.autotune(
                spmv_seed(), {"row": rows, "col": cols}, 48, 48,
                {"value": vals}, {"x": x}, jnp.zeros(48, jnp.float32),
                iters=2, measure_wrap=wrap, cache_extra="test:flaky-ref")
        assert result.picked_by == "measurement"
        assert np.isfinite(result.best_us)
        errs = [m for m in result.measurements if m.error is not None]
        assert len(errs) == 1
        assert result.best != errs[0].candidate
        assert all(np.isfinite(m.us_per_call) for m in result.measurements
                   if m.error is None)

    def test_timing_outliers_still_pick_viable(self):
        with faults.timing_outliers(period=3, spike_us=50_000.0):
            A, y = _small_spmv(backend="auto")
        assert A.tuning.picked_by == "measurement"
        best = [m for m in A.tuning.measurements
                if m.candidate == A.tuning.best]
        assert best[0].ok and np.isfinite(best[0].us_per_call)
        _, y_ref = _small_spmv()
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- fixpoint health
class TestFixpointHealth:
    def _poisoned_sssp(self, driver):
        # -inf weight: relaxing through it produces -inf, and from an
        # unreached source inf + (-inf) = NaN — either poisons (min, +)
        src = np.array([0, 1]); dst = np.array([1, 2])
        w = np.array([1.0, -np.inf], np.float32)
        return SSSP.from_edges(src, dst, w, 3, validate="off",
                               driver=driver)

    @pytest.mark.parametrize("driver", ["resident", "host"])
    def test_poisoned_fixpoint_stops_early(self, driver):
        s = self._poisoned_sssp(driver)
        s.run(0)
        rep = s.convergence
        assert rep.diverged and not rep.converged and not rep.exhausted
        # the health flag stops the loop at the first poisoned sweep
        # instead of burning the full num_nodes+1 bound
        assert rep.sweeps == 1

    def test_poisoned_parity_host_vs_resident(self):
        a = self._poisoned_sssp("resident"); da = a.run(0)
        b = self._poisoned_sssp("host"); db = b.run(0)
        assert a.convergence == b.convergence
        np.testing.assert_array_equal(da, db)

    def test_default_strict_rejects_poison_at_ingestion(self):
        with pytest.raises(V.InputError):
            SSSP.from_edges(np.array([0]), np.array([1]),
                            np.array([np.inf], np.float32), 2)

    @pytest.mark.parametrize("driver", ["resident", "host"])
    def test_negative_cycle_detected(self, driver):
        src = np.array([0, 1, 2]); dst = np.array([1, 2, 0])
        w = np.array([1.0, 1.0, -3.0], np.float32)
        s = SSSP.from_edges(src, dst, w, 3, driver=driver)
        s.run(0)
        rep = s.convergence
        assert rep.negative_cycle and rep.exhausted
        assert not rep.converged and not rep.diverged

    def test_negative_weights_without_cycle_converge(self):
        src = np.array([0, 1]); dst = np.array([1, 2])
        w = np.array([-2.0, -3.0], np.float32)
        s = SSSP.from_edges(src, dst, w, 3)
        d = s.run(0)
        assert s.convergence.converged and not s.convergence.negative_cycle
        np.testing.assert_array_equal(d, [0.0, -2.0, -5.0])

    def test_capped_sweeps_report_exhausted_not_negative_cycle(self):
        # an exhausted run BELOW the Bellman-Ford bound proves nothing
        src = np.array([0, 1, 2, 3]); dst = np.array([1, 2, 3, 4])
        w = np.ones(4, np.float32)
        s = SSSP.from_edges(src, dst, w, 5)
        s.run(0, max_sweeps=2)
        rep = s.convergence
        assert rep.exhausted and not rep.negative_cycle

    def test_convergence_report_backcompat_aliases(self):
        src = np.array([0, 1]); dst = np.array([1, 2])
        b = BFS.from_edges(src, dst, 3)
        b.run(0)
        assert b.sweeps_run == b.convergence.sweeps > 0
        assert b.converged is True


# ----------------------------------------------- concurrent cache writers
@pytest.mark.slow
def test_concurrent_cache_writers(tmp_path):
    """4 processes race to tune + plan-cache the same matrix against the
    same directories: every process must succeed, and both caches must
    end up with exactly one valid entry each (atomic publish: last
    writer wins with a COMPLETE file, never a torn one)."""
    plan_dir = tmp_path / "plans"
    tune_dir = tmp_path / "tune"
    script = (
        "import numpy as np, jax.numpy as jnp\n"
        "from repro.core.apps import SpMV\n"
        "rng = np.random.default_rng(0)\n"
        "rows = rng.integers(0, 48, 256); cols = rng.integers(0, 48, 256)\n"
        "vals = rng.standard_normal(256).astype(np.float32)\n"
        "A = SpMV.from_coo(rows, cols, vals, (48, 48), backend='auto',\n"
        f"    plan_cache_dir={str(plan_dir)!r},\n"
        f"    tune_cache_dir={str(tune_dir)!r})\n"
        "x = rng.standard_normal(48).astype(np.float32)\n"
        "print(float(np.asarray(A.matvec(jnp.asarray(x))).sum()))\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"),
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", script], env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(4)]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{err}"
    sums = {out.strip().splitlines()[-1] for out, _ in outs}
    assert len(sums) == 1                        # identical results
    plans = list(plan_dir.glob("*.plan"))
    tunes = list(tune_dir.glob("tune-*.json"))
    assert len(plans) >= 1 and len(tunes) == 1
    # every published file is complete and loadable
    from repro.core import planio
    for f in plans:
        planio.load_plan(str(f))
    from repro.tune import cache as tcache
    entry = json.loads(tunes[0].read_text())
    assert entry["schema"] == tcache.SCHEMA and "choice" in entry
    assert not list(plan_dir.glob("*.tmp")) and \
        not list(tune_dir.glob("*.tmp"))
