"""Serving-layer contracts: coalescing, admission, parity, elasticity.

Three layers of coverage:

- **Pure units** (no devices): the Coalescer's strict-FIFO column
  packing (including the mapreduce refusal and overflow behavior), the
  metrics snapshot structure, and the at-construction validation of
  every string knob (EngineConfig / RunnerConfig / Policy / backend /
  ServeConfig) — one regression test per knob.
- **Bitwise parity** (subprocess, 4 forced host devices): a coalesced
  K-query batch answered through ONE device window is bitwise-identical,
  column by column, to K sequential single-query engine runs — under
  churn, under ``arrival="first"`` (same realized straggler set), and
  through the fused window driver.
- **Serving edge cases** (subprocess): empty-queue idle loop, bounded
  queue rejection with retry_after, deadline expiry before dispatch,
  deadline missed mid-window, ALL workers preempted (requests stall,
  survive, and complete after re-arrival), matvec+mapreduce coalescing
  refusal, and the asyncio front door.
"""

from collections import deque

import numpy as np
import pytest

from conftest import run_with_devices

from repro.api import EngineConfig, Policy
from repro.runtime.elastic_runner import RunnerConfig
from repro.serve import Coalescer, Request, ServeConfig, ServerMetrics
from repro.serve.server import SyntheticClock


def _req(rid, kind, operand, cols):
    return Request(rid=rid, kind=kind, operand=operand, cols=cols,
                   t_enqueue=0.0)


# ---------------------------------------------------------------------- #
# Coalescer units
# ---------------------------------------------------------------------- #
def test_coalescer_packs_fifo_into_fixed_width_operand():
    r = 8
    q = deque([
        _req(0, "matvec", np.ones(r, np.float32), 1),
        _req(1, "matmat", 2 * np.ones((r, 2), np.float32), 2),
        _req(2, "matvec", 3 * np.ones(r, np.float32), 1),
    ])
    batch = Coalescer(r, batch_cols=4).pack(q)
    assert not q                       # all three fit in 4 columns
    assert batch.kind == "linear"
    assert [req.rid for req in batch.requests] == [0, 1, 2]
    assert batch.col_spans == [(0, 1), (1, 3), (3, 4)]
    assert batch.operand.shape == (r, 4)
    assert batch.operand.dtype == np.float32
    # Columns carry each query; unused width would be zero-padded.
    assert np.array_equal(batch.operand[:, 0], np.ones(r))
    assert np.array_equal(batch.operand[:, 1:3], 2 * np.ones((r, 2)))
    assert np.array_equal(batch.operand[:, 3], 3 * np.ones(r))


def test_coalescer_pads_unused_columns_with_zeros():
    r = 4
    q = deque([_req(0, "matvec", np.ones(r, np.float32), 1)])
    batch = Coalescer(r, batch_cols=3).pack(q)
    assert batch.operand.shape == (r, 3)
    assert np.array_equal(batch.operand[:, 1:], np.zeros((r, 2)))


def test_coalescer_overflow_ends_batch_without_reordering():
    r = 4
    # The 2-column matmat does not fit behind the matvec at batch_cols=2;
    # the narrow matvec BEHIND it must not jump the queue.
    q = deque([
        _req(0, "matvec", np.ones(r, np.float32), 1),
        _req(1, "matmat", np.ones((r, 2), np.float32), 2),
        _req(2, "matvec", np.ones(r, np.float32), 1),
    ])
    c = Coalescer(r, batch_cols=2)
    b0 = c.pack(q)
    assert [req.rid for req in b0.requests] == [0]
    b1 = c.pack(q)
    assert [req.rid for req in b1.requests] == [1]
    b2 = c.pack(q)
    assert [req.rid for req in b2.requests] == [2]
    assert b0.batch_id < b1.batch_id < b2.batch_id


def test_coalescer_refuses_to_merge_mapreduce_with_linear():
    r = 4
    q = deque([
        _req(0, "matvec", np.ones(r, np.float32), 1),
        _req(1, "mapreduce", None, 0),
        _req(2, "matvec", np.ones(r, np.float32), 1),
    ])
    c = Coalescer(r, batch_cols=8)
    b0 = c.pack(q)      # matvec alone: the mapreduce head ends the batch
    assert b0.kind == "linear" and [x.rid for x in b0.requests] == [0]
    b1 = c.pack(q)
    assert b1.kind == "mapreduce" and [x.rid for x in b1.requests] == [1]
    assert b1.operand is None
    b2 = c.pack(q)
    assert b2.kind == "linear" and [x.rid for x in b2.requests] == [2]
    assert c.pack(q) is None


# ---------------------------------------------------------------------- #
# Metrics units
# ---------------------------------------------------------------------- #
def test_metrics_snapshot_percentiles_and_goodput():
    m = ServerMetrics()
    lats = [0.1, 0.2, 0.3, 0.4]
    m.on_enqueue(0.0, depth=1)
    for i, lat in enumerate(lats):
        m.on_complete(lat, t_complete=1.0 + i, missed=(i == 3))
    m.on_reject()
    m.on_expire()
    m.on_idle()
    m.on_batch(3, 4)
    snap = m.snapshot()
    assert snap["requests"] == {
        "enqueued": 1, "completed": 4, "rejected": 1, "expired": 1,
        "deadline_missed": 1}
    assert snap["latency"]["n"] == 4
    assert snap["latency"]["p50"] == pytest.approx(
        float(np.percentile(lats, 50)))
    assert snap["latency"]["p99"] == pytest.approx(
        float(np.percentile(lats, 99)))
    # Goodput counts only within-deadline completions over the active span
    # (first enqueue at t=0, last completion at t=4): 3 / 4.
    assert snap["goodput_rps"] == pytest.approx(3 / 4.0)
    assert snap["batches"]["count"] == 1
    assert snap["batches"]["mean_requests"] == 3.0


def test_synthetic_clock_is_explicit_and_monotonic():
    clk = SyntheticClock(5.0)
    assert clk.now() == 5.0
    clk.advance(1.5)
    assert clk.now() == 6.5
    with pytest.raises(ValueError, match="backwards"):
        clk.advance(-1.0)


# ---------------------------------------------------------------------- #
# String-knob validation: one regression test per knob, all asserting the
# error is raised AT CONSTRUCTION and names both the bad value and the
# allowed set.
# ---------------------------------------------------------------------- #
def test_engine_config_rejects_bad_arrival():
    with pytest.raises(ValueError, match=r"arrival.*barrier.*'sometimes'"):
        EngineConfig(arrival="sometimes")


def test_engine_config_rejects_bad_replan():
    with pytest.raises(ValueError, match=r"replan.*central.*'p2p'"):
        EngineConfig(replan="p2p")


def test_engine_config_rejects_bad_verify():
    with pytest.raises(ValueError, match=r"verify.*exact.*'bitwise'"):
        EngineConfig(verify="bitwise")


def test_runner_config_rejects_bad_arrival():
    with pytest.raises(ValueError, match=r"arrival.*first.*'last'"):
        RunnerConfig(arrival="last")


def test_runner_config_rejects_bad_replan():
    with pytest.raises(ValueError, match=r"replan.*decentral.*'none'"):
        RunnerConfig(replan="none")


def test_runner_config_rejects_bad_verify():
    with pytest.raises(ValueError, match=r"verify.*allclose.*'yes'"):
        RunnerConfig(verify="yes")


def test_policy_rejects_bad_placement():
    with pytest.raises(ValueError, match=r"placement.*cyclic.*'ring'"):
        Policy(placement="ring")


def test_policy_rejects_bad_replan():
    with pytest.raises(ValueError, match=r"replan.*decentral.*'gossip'"):
        Policy(replan="gossip")


def test_engine_rejects_bad_backend():
    from repro.api import ElasticEngine, MatVec

    with pytest.raises(ValueError, match=r"backend.*simulate"):
        ElasticEngine(MatVec(), backend="gpu", n_machines=4)


def test_serve_config_rejects_bad_bounds():
    with pytest.raises(ValueError, match="batch_cols"):
        ServeConfig(batch_cols=0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=0)


# ---------------------------------------------------------------------- #
# Bitwise parity: coalesced batch == sequential single-query runs
# ---------------------------------------------------------------------- #
def test_coalesced_batch_bitwise_equals_sequential_runs():
    """K queries answered as columns of ONE window vs K fresh engines
    answering them one at a time — same policy, same churn event, same
    clocks. Bitwise per column, under barrier AND first-arrival, both
    stepwise and through the fused window driver (the serving dispatch
    path). Under ``arrival="first"`` the realized straggler set must
    also agree: row loads (and so modeled arrival order) depend on the
    plan, not on the operand width."""
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatMat, Policy
from repro.core.elastic import ElasticEvent
from repro.runtime.elastic_runner import SyntheticSpeedClock, make_exact_matrix

BASE = [1000., 1400., 1900., 2600.]
X = make_exact_matrix(4 * 96, 0)
q = X.shape[0]
rng = np.random.default_rng(1)
K = 4
W = rng.integers(-3, 4, size=(q, K)).astype(np.float32)
EV = ElasticEvent(step=0, preempted=(1,), arrived=(), available=(0, 2, 3))

def engine(arrival, fuse):
    return ElasticEngine(
        MatMat(),
        Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, arrival=arrival, fuse_steps=fuse,
                     initial_speeds=tuple(BASE)),
        backend="device", n_machines=4,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))

for arrival in ("barrier", "first"):
    for fuse in (1, 4):
        eng = engine(arrival, fuse); eng.prepare(X)
        Y, reps = eng.submit(W, event=EV)
        assert reps[0].jit_cache_size == 1
        for j in range(K):
            e2 = engine(arrival, fuse); e2.prepare(X)
            yj, rj = e2.submit(W[:, j:j+1], event=EV)
            assert np.asarray(Y)[:, j].tobytes() == \\
                np.asarray(yj)[:, 0].tobytes(), (arrival, fuse, j)
            assert rj[0].straggled == reps[0].straggled
        if arrival == "first":
            assert reps[0].straggled, "first-arrival should realize a straggler"
print("PARITY_OK")
""", n_devices=4)
    assert "PARITY_OK" in out


def test_server_serves_mixed_traffic_under_churn_bitwise():
    """End-to-end through the server: a mixed matvec/matmat/mapreduce
    trace with a preemption and a re-arrival mid-stream. Every response
    is checked against the float64 host reference (bitwise on the exact
    integer data), both lanes hold the jit-cache-of-1 invariant across
    the churn, and the metrics account for every request."""
    out = run_with_devices("""
import numpy as np
import jax.numpy as jnp
from repro.api import EngineConfig, MapReduceRows, Policy
from repro.runtime.elastic_runner import SyntheticSpeedClock, make_exact_matrix
from repro.serve import ElasticServer, ServeConfig, SyntheticClock

BASE = [1000., 1400., 1900., 2600.]
X = make_exact_matrix(4 * 96, 0)
q = X.shape[0]
X64 = X.astype(np.float64)
rng = np.random.default_rng(3)

mr = MapReduceRows(
    row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2, axis=1,
                                  keepdims=True),
    reduce_fn=lambda mapped: float(mapped.sum()),
    out_cols=1,
    ref_row_fn=lambda x64, _w: np.sum(x64 ** 2, axis=1, keepdims=True))
srv = ElasticServer(
    X,
    Policy(placement="cyclic", replication=3, stragglers=1),
    EngineConfig(block_rows=16, verify="exact", initial_speeds=tuple(BASE)),
    ServeConfig(batch_cols=4, max_queue=32),
    mapreduce=mr,
    clock=SyntheticClock(),
    engine_clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0),
    n_machines=4)

expect = {}
collected = []
for i in range(12):
    if i == 4:
        srv.feed_event(preempted=(1,))
    if i == 8:
        srv.feed_event(arrived=(1,))
    if i % 4 == 3:
        srv.submit("mapreduce")
        expect[i] = ("mapreduce", None)
    elif i % 4 == 2:
        w = rng.integers(-3, 4, size=(q, 2)).astype(np.float32)
        srv.submit("matmat", w)
        expect[i] = ("matmat", w)
    else:
        w = rng.integers(-3, 4, size=q).astype(np.float32)
        srv.submit("matvec", w)
        expect[i] = ("matvec", w)
    # Scheduling interleaves with arrivals, so both lanes dispatch while
    # the fleet is degraded (steps 4-7) — the churn reaches each lane as
    # a synthesized net event at its next dispatch.
    collected.extend(srv.poll())
collected.extend(srv.drain())
resps = {r.rid: r for r in collected}
assert sorted(resps) == list(range(12))

snap = srv.metrics_snapshot()
assert snap["requests"]["enqueued"] == 12
assert snap["requests"]["completed"] == 12
assert snap["requests"]["rejected"] == 0
assert snap["requests"]["expired"] == 0
for name, lane in snap["lanes"].items():
    assert lane["jit_cache_size"] == 1, (name, lane)
    assert lane["churn_events"] >= 1, (name, lane)  # both lanes saw churn

for rid, r in resps.items():
    kind, w = expect[rid]
    assert r.status == "ok"
    if kind in ("matvec", "matmat"):
        assert np.array_equal(r.result.astype(np.float64), X64 @ w)
    else:
        assert r.result == float(np.sum(X64 ** 2))
print("SERVE_CHURN_OK", len(resps))
""", n_devices=4)
    assert "SERVE_CHURN_OK" in out


def test_serving_edge_cases():
    """Admission/elasticity corners, one subprocess: idle loop, bounded
    queue rejection, deadline expiry pre-dispatch, deadline missed
    mid-window, total preemption (requests survive and complete after
    re-arrival), and the async front door."""
    out = run_with_devices("""
import asyncio
import numpy as np
from repro.api import EngineConfig, Policy
from repro.runtime.elastic_runner import SyntheticSpeedClock, make_exact_matrix
from repro.serve import AsyncElasticServer, ElasticServer, ServeConfig, SyntheticClock

BASE = [1000., 1400., 1900., 2600.]
X = make_exact_matrix(4 * 96, 0)
q = X.shape[0]
X64 = X.astype(np.float64)

def server(**kw):
    return ElasticServer(
        X,
        Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, initial_speeds=tuple(BASE)),
        ServeConfig(**kw),
        clock=SyntheticClock(),
        engine_clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0),
        n_machines=4)

w = np.ones(q, np.float32)

# --- empty queue: idle ticks, no dispatch, no responses -------------- #
srv = server(batch_cols=2, max_queue=4)
for _ in range(3):
    assert srv.poll() == []
snap = srv.metrics_snapshot()
assert snap["queue"]["idle_polls"] == 3
assert snap["windows"]["count"] == 0
print("IDLE_OK")

# --- bounded queue: reject with retry_after -------------------------- #
srv = server(batch_cols=2, max_queue=2)
assert srv.submit("matvec", w).admitted
assert srv.submit("matvec", w).admitted
t3 = srv.submit("matvec", w)
assert not t3.admitted and t3.retry_after > 0
assert srv.metrics_snapshot()["requests"]["rejected"] == 1
assert srv.queue_depth == 2          # the rejected one never queued
srv.drain()
assert srv.submit("matvec", w).admitted   # space again after drain
print("REJECT_OK")

# --- deadline expiry BEFORE dispatch --------------------------------- #
srv = server(batch_cols=2, max_queue=4)
srv.submit("matvec", w, deadline=0.5)
srv.clock.advance(1.0)               # the deadline passes while queued
resps = srv.poll()
assert [r.status for r in resps] == ["expired"]
snap = srv.metrics_snapshot()
assert snap["requests"]["expired"] == 1
assert snap["windows"]["count"] == 0  # never dispatched
print("EXPIRE_OK")

# --- deadline missed MID-window: completes, flagged, counted --------- #
srv = server(batch_cols=2, max_queue=4)
srv.submit("matvec", w, deadline=1e-6)  # tighter than any window
resps = srv.drain()
assert len(resps) == 1 and resps[0].status == "ok"
assert resps[0].deadline_missed
assert np.array_equal(resps[0].result.astype(np.float64), X64 @ w)
snap = srv.metrics_snapshot()
assert snap["requests"]["deadline_missed"] == 1
assert snap["goodput_rps"] == 0.0     # no within-deadline completions
print("MISS_OK")

# --- ALL workers preempted: requests stall, survive, then complete --- #
srv = server(batch_cols=2, max_queue=4)
srv.submit("matvec", w)
srv.submit("matvec", 2 * w)
srv.feed_event(preempted=(0, 1, 2, 3))
assert not srv.serveable()
assert srv.poll() == [] and srv.drain() == []   # stall, not fail
assert srv.queue_depth == 2
assert srv.metrics_snapshot()["queue"]["stalled_polls"] >= 1
srv.feed_event(arrived=(0, 2))
# Two workers cover every tile (cyclic J=3) but S=1 needs TWO live
# holders per tile — still below the plan feasibility bar: keep stalling
# rather than crash the dispatch.
assert not srv.serveable()
assert srv.poll() == [] and srv.queue_depth == 2
srv.feed_event(arrived=(1,))          # 3 workers: 1+S holders everywhere
assert srv.serveable()
resps = srv.drain()
assert sorted(r.rid for r in resps) == [0, 1]
assert all(r.status == "ok" for r in resps)
assert np.array_equal(resps[0].result.astype(np.float64), X64 @ w)
assert np.array_equal(resps[1].result.astype(np.float64), X64 @ (2 * w))
print("SURVIVE_OK")

# --- async front door ------------------------------------------------ #
srv = server(batch_cols=4, max_queue=8)
asrv = AsyncElasticServer(srv)

async def drive():
    loop_task = asyncio.ensure_future(asrv.run())
    r1, r2 = await asyncio.gather(
        asrv.request("matvec", w), asrv.request("matvec", 3 * w))
    asrv.close()
    await loop_task
    return r1, r2

r1, r2 = asyncio.run(drive())
assert r1.status == "ok" and r2.status == "ok"
assert np.array_equal(r1.result.astype(np.float64), X64 @ w)
assert np.array_equal(r2.result.astype(np.float64), X64 @ (3 * w))
print("ASYNC_OK")
print("EDGE_OK")
""", n_devices=4)
    for marker in ("IDLE_OK", "REJECT_OK", "EXPIRE_OK", "MISS_OK",
                   "SURVIVE_OK", "ASYNC_OK", "EDGE_OK"):
        assert marker in out


def test_async_close_fails_all_pending_waiters():
    """Shutdown-hygiene regression: close() must resolve EVERY pending
    waiter with a terminal "shutdown" response immediately — not leave
    them awaiting a run-loop iteration that never comes — and a request
    made after close resolves the same way without touching the queue."""
    out = run_with_devices("""
import asyncio
import numpy as np
from repro.api import EngineConfig, Policy
from repro.serve import (AsyncElasticServer, ElasticServer, ServeConfig,
                         SyntheticClock)

rng = np.random.default_rng(0)
X = rng.standard_normal((4 * 24, 32)).astype(np.float32)
srv = ElasticServer(
    X, policy=Policy(placement="cyclic", replication=2, stragglers=1),
    engine_cfg=EngineConfig(block_rows=8),
    serve_cfg=ServeConfig(batch_cols=4),
    clock=SyntheticClock(), n_machines=4)
srv.feed_event(preempted=[2])     # unserveable: requests pend forever

async def main():
    asrv = AsyncElasticServer(srv, idle_sleep=0.0)
    loop_task = asyncio.ensure_future(asrv.run())
    reqs = [asyncio.ensure_future(
        asrv.request("matvec", rng.standard_normal(32).astype(np.float32)))
        for _ in range(3)]
    await asyncio.sleep(0.05)
    assert not any(r.done() for r in reqs)    # genuinely pending
    asrv.close()
    resps = await asyncio.wait_for(asyncio.gather(*reqs), timeout=2)
    assert [r.status for r in resps] == ["shutdown"] * 3
    assert {r.kind for r in resps} == {"matvec"}
    await asyncio.wait_for(loop_task, timeout=2)  # run() exits cleanly
    assert asrv._waiters == {}
    post = await asrv.request("matvec", np.zeros(32, np.float32))
    assert post.status == "shutdown"
    assert srv.queue_depth == 3    # nothing new was admitted after close

asyncio.run(main())
print("SHUTDOWN_OK")
""", n_devices=4)
    assert "SHUTDOWN_OK" in out
