"""Elastic serving layer: coalesced query traffic on one elastic fleet.

Drives :class:`repro.serve.ElasticServer` — the multi-tenant front door
over a single staged operand — through seeded synthetic request traces
and emits ``BENCH_serve.json``. Every scenario runs on the deterministic
clock pair (``SyntheticClock`` for arrivals/latencies,
``SyntheticSpeedClock`` with ``jitter_sigma=0`` for modeled device
time), so the latency/goodput numbers are *modeled* and bit-identical
across runs; only ``wall_s`` reflects the host.

Scenarios:

- **steady**: matvec/matmat mix, no membership change — the coalescer's
  packing density and the latency distribution under a quiet fleet;
- **churn**: same trace with a mid-trace preemption (worker 1 leaves,
  returns 4 requests later) — churn lands as data (new plan arrays) on
  the same jit entry, and the lane counters prove it;
- **churn_first**: the churn trace under ``arrival="first"`` — the
  serving layer rides the first-N-results path, shaving the modeled
  straggler barrier out of every window.

Each scenario reports the server's structured metrics snapshot
(p50/p99/mean latency, goodput, queue/reject/expire/deadline counters,
batch packing stats, per-lane jit-cache and churn counters).

Run:  PYTHONPATH=src python benchmarks/bench_serve.py [--requests 24]
      PYTHONPATH=src python benchmarks/bench_serve.py --smoke
(--smoke: tiny structural run for CI — asserts jit_cache_size == 1 per
lane across a preempt/return cycle, zero rejects under no load, and
bitwise parity of a coalesced 4-query batch against 4 sequential
single-query engine runs, then exits. No timing assertions.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.launch.hostdev import (  # noqa: E402
    enable_compile_cache,
    ensure_host_devices,
    require_devices,
)

N_WORKERS = 4
ensure_host_devices(N_WORKERS)

import numpy as np  # noqa: E402

DIM = N_WORKERS * 96
BASE_SPEEDS = (1000.0, 1400.0, 1900.0, 2600.0)
BATCH_COLS = 8


def _mapreduce():
    import jax.numpy as jnp

    from repro.api import MapReduceRows

    return MapReduceRows(
        row_fn=lambda xb, w2: jnp.sum(xb.astype(jnp.float32) ** 2,
                                      axis=1, keepdims=True),
        reduce_fn=lambda mapped: float(mapped.sum()),
        out_cols=1,
        ref_row_fn=lambda x64, _w: np.sum(x64 ** 2, axis=1, keepdims=True),
        name="rows_sumsq",
    )


def _build_server(seed, arrival="barrier", fuse_steps=1, mapreduce=True,
                  deadline=None, max_queue=64):
    from repro.api import EngineConfig, Policy
    from repro.runtime.elastic_runner import (
        SyntheticSpeedClock,
        make_exact_matrix,
    )
    from repro.serve import ElasticServer, ServeConfig, SyntheticClock

    x = make_exact_matrix(DIM, seed)
    server = ElasticServer(
        x,
        Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, arrival=arrival, fuse_steps=fuse_steps,
                     initial_speeds=BASE_SPEEDS),
        ServeConfig(batch_cols=BATCH_COLS, max_queue=max_queue,
                    default_deadline=deadline),
        mapreduce=_mapreduce() if mapreduce else None,
        clock=SyntheticClock(),
        engine_clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.0,
                                         seed=seed),
        n_machines=N_WORKERS,
    )
    return server, x


def _trace(server, requests, seed, mean_gap=0.05, churn_at=None,
           mapreduce_every=7, poll_every=3):
    """Seeded trace: exponential gaps advance the synthetic clock, the
    server polls every ``poll_every`` arrivals (a burst window — lets the
    coalescer actually pack); churn (preempt worker 1, return 4 requests
    later) lands mid-trace when requested."""
    rng = np.random.default_rng(seed + 7)
    q = server.operand_rows
    responses = []
    for i in range(requests):
        if churn_at is not None and i == churn_at:
            server.feed_event(preempted=(1,))
        if churn_at is not None and i == churn_at + 4:
            server.feed_event(arrived=(1,))
        kind = ("matmat" if i % 5 == 4 else
                "mapreduce" if mapreduce_every and
                i % mapreduce_every == 2 else "matvec")
        if kind == "matvec":
            operand = rng.integers(-3, 4, size=q).astype(np.float32)
        elif kind == "matmat":
            c = int(rng.integers(2, BATCH_COLS // 2 + 1))
            operand = rng.integers(-3, 4, size=(q, c)).astype(np.float32)
        else:
            operand = None
        ticket = server.submit(kind, operand)
        if ticket.admitted:
            server.clock.advance(float(rng.exponential(mean_gap)))
            if i % poll_every == poll_every - 1:
                responses.extend(server.poll())
    responses.extend(server.drain())
    return responses


def _scenario(name, requests, seed, csv=True, **kw):
    t0 = time.perf_counter()
    server, _ = _build_server(seed, arrival=kw.pop("arrival", "barrier"))
    warm = np.ones(server.operand_rows, dtype=np.float32)
    server.submit("matvec", warm)
    server.drain()                    # cold start: jit + step-0 plan
    cold_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    responses = _trace(server, requests, seed, **kw)
    wall_s = time.perf_counter() - t1

    snap = server.metrics_snapshot()
    entry = {
        "snapshot": snap,
        "responses": {
            "ok": sum(r.status == "ok" for r in responses),
            "expired": sum(r.status == "expired" for r in responses),
        },
        "cold_start_s": cold_s,
        "wall_s": wall_s,
    }
    if csv:
        lat = snap["latency"]
        lanes = snap["lanes"]["linear"]
        print(f"serve_{name},"
              f"{1e6 * wall_s / max(requests, 1):.1f},"
              f"modeled p50 {lat['p50']:.4f} p99 {lat['p99']:.4f}; "
              f"goodput {snap['goodput_rps']:.1f} req/s; "
              f"{snap['batches']['mean_requests']:.2f} req/batch over "
              f"{snap['batches']['count']} batches; "
              f"jit entries {lanes['jit_cache_size']}, "
              f"{lanes['churn_events']} churn events")
    return entry


def run(requests: int = 24, seed: int = 0, out: str = "BENCH_serve.json",
        csv: bool = True):
    churn_at = max(2, requests // 3)
    scenarios = {
        "steady": _scenario("steady", requests, seed, csv=csv),
        "churn": _scenario("churn", requests, seed, csv=csv,
                           churn_at=churn_at),
        "churn_first": _scenario("churn_first", requests, seed, csv=csv,
                                 churn_at=churn_at, arrival="first"),
    }
    doc = {
        "benchmark": "elastic_serve",
        "n_workers": N_WORKERS,
        "dim": DIM,
        "batch_cols": BATCH_COLS,
        "requests": requests,
        "churn_at": churn_at,
        "seed": seed,
        "scenarios": scenarios,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    if csv:
        print(f"# wrote {out}")
    return doc


def run_smoke(seed: int = 0) -> None:
    """Structural CI tripwire for the serving layer — no timing asserts.

    1. Coalescing parity: 4 integer matvec queries land in ONE padded
       MatMat window; each response must be bitwise-equal to a fresh
       sequential single-query engine run on the same staged data.
    2. Churn survives on one jit entry: preempt worker 1, serve, return
       it, serve — per-lane ``jit_cache_size`` stays 1 and the runner
       counts the membership changes.
    3. Admission under no load rejects nothing.
    """
    from repro.api import ElasticEngine, EngineConfig, MatMat, Policy
    from repro.runtime.elastic_runner import SyntheticSpeedClock

    server, x = _build_server(seed, mapreduce=False)
    rng = np.random.default_rng(seed + 7)
    queries = [rng.integers(-3, 4, size=DIM).astype(np.float32)
               for _ in range(4)]

    for w in queries:
        server.submit("matvec", w)
    responses = server.poll()
    assert len(responses) == 4, [r.status for r in responses]
    assert len({r.batch_id for r in responses}) == 1, \
        "4 compatible matvecs must coalesce into one window"

    seq = ElasticEngine(
        MatMat(), Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, initial_speeds=BASE_SPEEDS),
        backend="device", n_machines=N_WORKERS,
        clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.0, seed=seed))
    seq.prepare(x)
    for resp, w in zip(responses, queries):
        y, _ = seq.submit(w[:, None])
        got = np.asarray(resp.result)
        want = np.asarray(y)[:, 0]
        assert got.tobytes() == want.tobytes(), \
            "coalesced column != sequential single-query run (bitwise)"

    server.feed_event(preempted=(1,))
    for w in queries[:2]:
        server.submit("matvec", w)
    assert len(server.poll()) == 2
    server.feed_event(arrived=(1,))
    for w in queries[2:]:
        server.submit("matvec", w)
    assert len(server.poll()) == 2

    snap = server.metrics_snapshot()
    lane = snap["lanes"]["linear"]
    assert lane["jit_cache_size"] == 1, \
        f"churn recompiled the serving executor: {lane['jit_cache_size']}"
    assert lane["churn_events"] >= 2, lane["churn_events"]
    assert snap["requests"]["rejected"] == 0, \
        "admission rejected requests with an empty fleet and a quiet queue"
    assert snap["requests"]["completed"] == 8
    assert snap["queue"]["depth"] == 0

    print(f"serve_smoke,0,coalesced 4-query window bitwise == sequential, "
          f"{lane['churn_events']} churn events on jit cache "
          f"{lane['jit_cache_size']}, 0 rejects, "
          f"{snap['batches']['count']} batches served")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", "--steps", type=int, default=24,
                    dest="requests",
                    help="trace length per scenario (--steps is the "
                         "harness-compat alias)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny structural-assertion run for CI")
    args = ap.parse_args()
    require_devices(N_WORKERS)
    enable_compile_cache()
    if args.smoke:
        run_smoke(seed=args.seed)
    else:
        run(requests=args.requests, seed=args.seed, out=args.out)
