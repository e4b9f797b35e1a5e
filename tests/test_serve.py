"""Concurrent query serving (``repro.serve.query``, DESIGN.md §12):
batched-vs-sequential bitwise equality, deadline/shed/breaker behavior
under injected faults and latency, multi-threaded client stress, and the
health-report schema.
"""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import graphs as GR
from repro.core.apps import SpMV
from repro.obs import metrics as M
from repro.serve import query as Q
from repro.sparse import generators as G
from repro.testing import faults


@pytest.fixture(scope="module")
def graph_case():
    return G.graph_case("powerlaw", 192, avg_deg=6, seed=7)


@pytest.fixture(scope="module")
def bfs_app(graph_case):
    c = graph_case
    return GR.BFS.from_edges(c.src, c.dst, c.num_nodes)


@pytest.fixture(scope="module")
def sssp_app(graph_case):
    c = graph_case
    return GR.SSSP.from_edges(c.src, c.dst, c.weight, c.num_nodes)


@pytest.fixture(scope="module")
def spmv_app():
    m = G.power_law(256, 6, seed=9)
    return SpMV.from_coo(m.rows, m.cols, m.vals, m.shape)


def _wait(ticket, clk=None, advance=0.0, timeout=30.0):
    """Poll a ticket to completion, optionally advancing a VirtualClock
    so backoff/cooldown gates pass without real sleeps."""
    deadline = time.monotonic() + timeout
    while not ticket.done():
        if clk is not None and advance:
            clk.advance(advance)
        time.sleep(0.002)
        if time.monotonic() > deadline:
            raise AssertionError("ticket never resolved")
    return ticket


# ----------------------------------------------------- correctness
def test_batched_equals_sequential_bitwise(bfs_app, sssp_app, spmv_app):
    """Every admitted request's result is bitwise-equal to its
    sequential single-request execution, across all three endpoints."""
    eng = Q.QueryEngine([Q.bfs_endpoint(bfs_app),
                         Q.sssp_endpoint(sssp_app),
                         Q.spmv_endpoint(spmv_app)],
                        queue_capacity=256)
    rng = np.random.default_rng(0)
    bfs_srcs = rng.integers(0, bfs_app.num_nodes, 9)
    sssp_srcs = rng.integers(0, sssp_app.num_nodes, 7)
    xs = rng.standard_normal(
        (5, spmv_app.shape[1])).astype(np.float32)
    with eng:
        tickets = ([("bfs", int(s), eng.submit("bfs", int(s)))
                    for s in bfs_srcs]
                   + [("sssp", int(s), eng.submit("sssp", int(s)))
                      for s in sssp_srcs]
                   + [("spmv", x, eng.submit("spmv", x)) for x in xs])
        for kind, payload, t in tickets:
            r = _wait(t).result(1)
            if kind == "bfs":
                assert np.array_equal(r.value, bfs_app.run(payload))
            elif kind == "sssp":
                assert np.array_equal(r.value, sssp_app.run(payload))
            else:
                ref = np.asarray(spmv_app.matvec(jnp.asarray(payload)))
                assert np.array_equal(np.asarray(r.value), ref)
            assert r.attempts == 1


def test_continuous_batching_coalesces(bfs_app):
    """Back-to-back requests ride one batched dispatch (batch_size > 1
    observed), and per-request slicing still matches sequential runs."""
    eng = Q.QueryEngine([Q.bfs_endpoint(bfs_app)], queue_capacity=64)
    with eng:
        eng.warmup("bfs", 0)
        tickets = [eng.submit("bfs", s) for s in range(12)]
        sizes = {_wait(t).result(1).batch_size for t in tickets}
    assert max(sizes) > 1, f"no coalescing observed: {sizes}"


# ----------------------------------------------------- deadlines
def test_deadline_expired_in_queue_never_dispatched():
    clk = faults.VirtualClock()
    calls = []
    ep = Q.Endpoint(name="echo", batch_fn=lambda ps: calls.append(
        list(ps)) or list(ps))
    eng = Q.QueryEngine([ep], clock=clk, poll_interval_s=0.05)
    with eng:
        t = eng.submit("echo", 1, deadline_s=0.01)
        clk.advance(1.0)      # expires before the dispatcher wakes
        _wait(t)
        with pytest.raises(Q.DeadlineExceeded) as ei:
            t.result(1)
    assert ei.value.stage == "queued"
    assert ei.value.request_id
    assert not any(1 in c for c in calls), "expired request was dispatched"


def test_inflight_overrun_is_recorded_straggler():
    clk = faults.VirtualClock()
    ep = Q.Endpoint(name="echo", batch_fn=lambda ps: list(ps))
    eng = Q.QueryEngine([ep], clock=clk)
    before = M.value("serve.deadline.inflight")
    with eng, faults.slow_calls((ep, "batch_fn"), 0.5, clock=clk):
        t = eng.submit("echo", 1, deadline_s=0.1)
        _wait(t)
        with pytest.raises(Q.DeadlineExceeded) as ei:
            t.result(1)
    assert ei.value.stage == "inflight"
    assert ei.value.overrun_s == pytest.approx(0.4)
    assert M.value("serve.deadline.inflight") == before + 1


# ----------------------------------------------------- shedding
def test_bounded_queue_sheds_loudly():
    clk = faults.VirtualClock()
    ep = Q.Endpoint(name="echo", batch_fn=lambda ps: list(ps))
    # poll_interval long enough that nothing drains while we flood
    eng = Q.QueryEngine([ep], clock=clk, queue_capacity=3,
                        poll_interval_s=5.0)
    shed = []
    admitted = []
    for i in range(10):
        try:
            admitted.append(eng.submit("echo", i))
        except Q.RejectedError as e:
            shed.append(e)
    assert len(shed) == 7 and len(admitted) == 3
    assert all(e.capacity == 3 and e.queue_depth == 3 for e in shed)
    h = eng.health()
    assert h["counters"]["shed"] == 7
    assert h["ready"] is False      # queue full => not ready
    eng.close()
    # admitted requests were still served on close(drain=True)
    assert [t.result(5).value for t in admitted] == [0, 1, 2]


# ----------------------------------------------------- retry/backoff
def test_retry_with_backoff_on_degradable_fault():
    clk = faults.VirtualClock()
    state = {"calls": 0}

    def torn_then_fine(ps):
        state["calls"] += 1
        if state["calls"] <= 2:
            raise OSError("torn tuning cache entry mid-flight")
        return [p * 10 for p in ps]

    ep = Q.Endpoint(name="flaky", batch_fn=torn_then_fine)
    eng = Q.QueryEngine([ep], clock=clk, backoff_s=0.01,
                        backoff_cap_s=0.05, max_retries=3,
                        breaker_threshold=10)
    before = M.value("degradation.serve.retryable_fault")
    with eng:
        t = eng.submit("flaky", 7)
        r = _wait(t, clk=clk, advance=0.05).result(1)
    assert r.value == 70
    assert r.attempts == 3
    assert M.value("degradation.serve.retryable_fault") == before + 2
    kinds = [e.kind for e in eng.degradations]
    assert kinds.count("retryable_fault") == 2


def test_retries_exhausted_surfaces_original_error():
    clk = faults.VirtualClock()

    def always_torn(ps):
        raise OSError("torn forever")

    ep = Q.Endpoint(name="torn", batch_fn=always_torn)
    eng = Q.QueryEngine([ep], clock=clk, backoff_s=0.01, max_retries=1,
                        breaker_threshold=100)
    with eng:
        t = eng.submit("torn", 1)
        _wait(t, clk=clk, advance=0.05)
        with pytest.raises(OSError, match="torn forever"):
            t.result(1)


def test_nonretryable_fault_fails_fast():
    clk = faults.VirtualClock()

    def boom(ps):
        raise RuntimeError("executor exploded")

    ep = Q.Endpoint(name="boom", batch_fn=boom)
    eng = Q.QueryEngine([ep], clock=clk, breaker_threshold=100)
    with eng:
        t = eng.submit("boom", 1)
        _wait(t)
        with pytest.raises(RuntimeError, match="executor exploded"):
            t.result(1)


# ----------------------------------------------------- circuit breaker
def test_breaker_trips_serves_unavailable_and_half_open_recovers():
    clk = faults.VirtualClock()
    state = {"fail": True}

    def sometimes(ps):
        if state["fail"]:
            raise RuntimeError("backend fault")
        return list(ps)

    ep = Q.Endpoint(name="ep", batch_fn=sometimes)
    eng = Q.QueryEngine([ep], clock=clk, breaker_threshold=2,
                        breaker_cooldown_s=10.0)
    with eng:
        for i in range(2):
            t = eng.submit("ep", i)
            _wait(t)
            with pytest.raises(RuntimeError):
                t.result(1)
        h = eng.health()
        assert h["breaker"]["state"] == "open"
        assert h["breaker"]["consecutive_faults"] == 2
        assert "backend fault" in h["breaker"]["last_fault"]
        assert h["ready"] is False
        with pytest.raises(Q.Unavailable) as ei:
            eng.submit("ep", 9)
        assert ei.value.breaker == "open"
        assert ei.value.retry_after_s > 0
        assert any(e.kind == "breaker_open" for e in eng.degradations)

        # half-open probe: a still-failing probe re-opens the breaker
        clk.advance(11.0)
        t = eng.submit("ep", 1)
        _wait(t)
        with pytest.raises(RuntimeError):
            t.result(1)
        assert eng.health()["breaker"]["state"] == "open"

        # a succeeding probe closes it and traffic resumes
        state["fail"] = False
        clk.advance(11.0)
        t = eng.submit("ep", 5)
        assert _wait(t).result(1).value == 5
        assert eng.health()["breaker"]["state"] == "closed"
        assert eng.health()["ready"] is True


def test_half_open_probes_one_request_at_a_time():
    clk = faults.VirtualClock()
    sizes = []
    state = {"fail": True}

    def fn(ps):
        if state["fail"]:
            raise RuntimeError("x")
        sizes.append(len(ps))
        return list(ps)

    ep = Q.Endpoint(name="ep", batch_fn=fn)
    eng = Q.QueryEngine([ep], clock=clk, breaker_threshold=1,
                        breaker_cooldown_s=5.0, poll_interval_s=0.005)
    with eng:
        t0 = eng.submit("ep", 0)
        _wait(t0)
        with pytest.raises(RuntimeError):
            t0.result(1)
        assert eng.health()["breaker"]["state"] == "open"
        state["fail"] = False
        clk.advance(6.0)           # half-open on next tick
        ts = [eng.submit("ep", i) for i in range(4)]
        for t in ts:
            _wait(t)
        assert sizes[0] == 1, f"probe batched {sizes[0]} requests"
        assert [t.result(1).value for t in ts] == [0, 1, 2, 3]


# ----------------------------------------------------- overload e2e
def test_overload_2x_sheds_and_serves_admitted_bitwise(bfs_app):
    """The acceptance scenario: 2x overload with injected latency —
    the excess is shed/deadline-failed loudly (structured errors with
    queue state) while every ADMITTED request returns a result
    bitwise-equal to its sequential execution."""
    clk = faults.VirtualClock()
    cap = 8
    ep = Q.bfs_endpoint(bfs_app, max_batch=4)
    eng = Q.QueryEngine([ep], clock=clk, queue_capacity=cap,
                        poll_interval_s=5.0)   # hold dispatch: flood first
    outcomes = {"served": [], "shed": [], "deadline": []}
    with eng, faults.slow_calls((ep, "batch_fn"), 0.2, clock=clk):
        tickets = []
        for s in range(2 * cap):               # 2x the queue capacity
            try:
                tickets.append((s, eng.submit("bfs", s, deadline_s=30.0)))
            except Q.RejectedError as e:
                assert e.queue_depth == cap
                outcomes["shed"].append(s)
        for s, t in tickets:
            try:
                r = _wait(t).result(1)
                assert np.array_equal(r.value, bfs_app.run(s)), s
                outcomes["served"].append(s)
            except Q.DeadlineExceeded:
                outcomes["deadline"].append(s)
    assert len(outcomes["shed"]) == cap            # the 2x excess shed
    assert len(outcomes["served"]) == cap          # everyone admitted served
    assert not outcomes["deadline"]
    h = eng.health()
    assert h["counters"]["shed"] == cap


# ----------------------------------------------------- client stress
def test_multithreaded_clients_no_lost_or_duplicated_responses(bfs_app):
    """>= 4 producer threads hammering one engine: every request gets
    exactly one response, ids are unique, and each response is correct
    for ITS request (no cross-request slicing mixups)."""
    eng = Q.QueryEngine([Q.bfs_endpoint(bfs_app, max_batch=16)],
                        queue_capacity=512)
    n_threads, per_thread = 6, 20
    results: dict[str, tuple] = {}
    errors: list = []
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng(tid)
        pairs = []
        for i in range(per_thread):
            s = int(rng.integers(0, bfs_app.num_nodes))
            pairs.append((s, eng.submit(
                "bfs", s, request_id=f"t{tid}-{i}")))
        for s, t in pairs:
            try:
                r = t.result(60)
                with lock:
                    results[r.request_id] = (s, r)
            except Exception as e:  # noqa: BLE001 — collected for assert
                with lock:
                    errors.append(e)

    with eng:
        eng.warmup("bfs", 0)
        threads = [threading.Thread(target=client, args=(tid,))
                   for tid in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors, errors
    assert len(results) == n_threads * per_thread    # none lost, none duped
    served = eng.health()["counters"]["served"]
    assert served == n_threads * per_thread + 1      # + warmup
    by_source: dict[int, np.ndarray] = {}
    for rid, (s, r) in results.items():
        ref = by_source.setdefault(s, bfs_app.run(s))
        assert np.array_equal(r.value, ref), rid


# ----------------------------------------------------- health schema
def test_health_report_schema(bfs_app):
    eng = Q.QueryEngine([Q.bfs_endpoint(bfs_app)], queue_capacity=4)
    with eng:
        eng.warmup("bfs", 0)
        h = eng.health()
    assert set(h) >= {"ready", "queue_depth", "capacity", "inflight",
                      "closed", "breaker", "endpoints", "counters"}
    assert set(h["breaker"]) == {"state", "consecutive_faults",
                                 "cooldown_remaining_s", "last_fault"}
    ep = h["endpoints"]["bfs"]
    assert set(ep) == {"fingerprint", "max_batch", "tuned", "warm",
                       "batches_served"}
    assert ep["warm"] is True and ep["batches_served"] >= 1
    assert ep["fingerprint"].startswith("bfs_relax:")
    assert isinstance(h["counters"], dict)
    assert h["ready"] in (True, False)
    # closed engine is not ready and rejects with EngineClosed
    assert eng.health()["closed"] is True
    with pytest.raises(Q.EngineClosed):
        eng.submit("bfs", 0)


def test_unknown_endpoint_rejected(bfs_app):
    with Q.QueryEngine([Q.bfs_endpoint(bfs_app)]) as eng:
        with pytest.raises(ValueError, match="unknown endpoint"):
            eng.submit("nope", 0)


# ----------------------------------------------------- fault injectors
def test_slow_calls_path_mode_advances_virtual_clock(tmp_path):
    clk = faults.VirtualClock()
    p = tmp_path / "cache" / "entry.bin"
    p.parent.mkdir()
    p.write_bytes(b"x")
    with faults.slow_calls(tmp_path, 0.25, clock=clk):
        with open(p, "rb") as f:
            f.read()
    assert clk() == pytest.approx(0.25)
    # thread-scoped: another thread's opens are NOT delayed
    t0 = clk()

    def other():
        with open(p, "rb") as f:
            f.read()

    with faults.slow_calls(tmp_path, 0.25, clock=clk):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert clk() == t0


def test_slow_calls_restores_on_exit():
    ep = Q.Endpoint(name="e", batch_fn=lambda ps: list(ps))
    original = ep.batch_fn
    with faults.slow_calls((ep, "batch_fn"), 0.1,
                           clock=faults.VirtualClock()):
        assert ep.batch_fn is not original
    assert ep.batch_fn is original
