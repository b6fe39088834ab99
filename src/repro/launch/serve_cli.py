"""CLI for the elastic serving layer: run a query trace, print metrics.

Launches an :class:`~repro.serve.ElasticServer` over an exact integer
demo matrix on four devices (four chips on a TPU host, forced host devices
on the CPU), pushes a seeded synthetic request
trace (matvec/matmat mix, Poisson-ish arrivals) through it — optionally
with a mid-trace churn event — and prints the structured metrics
snapshot (p50/p99 latency, goodput, queue/reject/deadline counters) as
JSON. The deterministic synthetic clocks make two runs with the same
arguments print identical numbers.

Run:
  PYTHONPATH=src python -m repro.launch.serve_cli --requests 32 \\
      --churn-at 8 --deadline 2.0
"""

from __future__ import annotations

import argparse
import json

from repro.launch.hostdev import (
    enable_compile_cache,
    ensure_host_devices,
    require_devices,
)

N_WORKERS = 4
BASE_SPEEDS = (1000.0, 1400.0, 1900.0, 2600.0)


def build_server(args):
    import numpy as np

    from repro.api import EngineConfig, MapReduceRows, Policy
    from repro.runtime.elastic_runner import (
        SyntheticSpeedClock,
        make_exact_matrix,
    )
    from repro.serve import ElasticServer, ServeConfig, SyntheticClock

    x = make_exact_matrix(args.dim, args.seed)

    fault_injector = None
    verify_results = "off"
    if args.corruption_rate > 0:
        from repro.faults import ChaosPlan, FaultInjector

        # One step index per potential window: a seeded schedule of
        # silent result corruptions for the linear lane's runner, audited
        # end-to-end by the server (verify_results="always") — detected
        # windows requeue and retry clean, and the snapshot's integrity
        # counters record the whole story deterministically.
        n_faults = max(1, round(args.corruption_rate * args.requests))
        plan = ChaosPlan.generate(
            max(args.requests, 1), N_WORKERS, n_faults=n_faults,
            kinds=("result_corruption",), seed=args.seed + 13)
        fault_injector = FaultInjector(plan)
        verify_results = "always"

    def _mapreduce():
        import jax.numpy as jnp

        return MapReduceRows(
            row_fn=lambda xb, w2: jnp.sum(
                xb.astype(jnp.float32) ** 2, axis=1, keepdims=True),
            reduce_fn=lambda mapped: float(mapped.sum()),
            out_cols=1,
            ref_row_fn=lambda x64, _w: np.sum(
                x64 ** 2, axis=1, keepdims=True),
            name="rows_sumsq",
        )

    server = ElasticServer(
        x,
        Policy(placement="cyclic", replication=3,
               stragglers=args.stragglers),
        EngineConfig(block_rows=16, arrival=args.arrival,
                     fuse_steps=args.fuse_steps, verify=args.verify,
                     initial_speeds=BASE_SPEEDS),
        ServeConfig(batch_cols=args.batch_cols, max_queue=args.max_queue,
                    default_deadline=args.deadline,
                    verify_results=verify_results),
        mapreduce=_mapreduce(),
        clock=SyntheticClock(),
        engine_clock=SyntheticSpeedClock(BASE_SPEEDS, jitter_sigma=0.0,
                                         seed=args.seed),
        n_machines=N_WORKERS,
        fault_injector=fault_injector,
    )
    return server, x


def run_trace(server, args):
    """Seeded request trace: exponential inter-arrival gaps advance the
    synthetic clock, the server polls between arrivals, churn (one
    preemption, later re-arrival) lands mid-trace."""
    import numpy as np

    rng = np.random.default_rng(args.seed + 7)
    q = server.operand_rows
    responses = []
    for i in range(args.requests):
        if args.churn_at is not None and i == args.churn_at:
            server.feed_event(preempted=(1,))
        if args.churn_at is not None and i == args.churn_at + 4:
            server.feed_event(arrived=(1,))
        kind = ("matmat" if i % 5 == 4 else
                "mapreduce" if args.mapreduce_every and
                i % args.mapreduce_every == 2 else "matvec")
        if kind == "matvec":
            operand = rng.integers(-3, 4, size=q).astype(np.float32)
        elif kind == "matmat":
            c = int(rng.integers(2, max(3, args.batch_cols // 2 + 1)))
            operand = rng.integers(-3, 4, size=(q, c)).astype(np.float32)
        else:
            operand = None
        ticket = server.submit(kind, operand)
        if not ticket.admitted:
            continue
        server.clock.advance(float(rng.exponential(args.mean_gap)))
        responses.extend(server.poll())
    responses.extend(server.drain())
    return responses


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dim", type=int, default=N_WORKERS * 96)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch-cols", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline (clock units from enqueue)")
    ap.add_argument("--mean-gap", type=float, default=0.05,
                    help="mean synthetic inter-arrival gap")
    ap.add_argument("--churn-at", type=int, default=None,
                    help="preempt worker 1 before this request index "
                         "(returns 4 requests later)")
    ap.add_argument("--stragglers", type=int, default=1)
    ap.add_argument("--arrival", choices=("barrier", "first"),
                    default="barrier")
    ap.add_argument("--fuse-steps", type=int, default=1)
    ap.add_argument("--verify", choices=("exact", "allclose"), default=None)
    ap.add_argument("--mapreduce-every", type=int, default=0,
                    help="every Nth request is a mapreduce query (0 = none)")
    ap.add_argument("--corruption-rate", type=float, default=0.0,
                    help="fraction of the trace hit by seeded silent "
                         "result corruption (>0 turns the server's "
                         "Freivalds window audit on; detected windows "
                         "requeue and retry clean)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not 0.0 <= args.corruption_rate <= 1.0:
        ap.error(f"--corruption-rate must be in [0, 1], "
                 f"got {args.corruption_rate}")

    ensure_host_devices(N_WORKERS)
    require_devices(N_WORKERS)
    enable_compile_cache()
    server, _ = build_server(args)
    responses = run_trace(server, args)
    snap = server.metrics_snapshot()
    snap["responses"] = {
        "ok": sum(r.status == "ok" for r in responses),
        "expired": sum(r.status == "expired" for r in responses),
    }
    print(json.dumps(snap, indent=2, sort_keys=True))
    return snap


if __name__ == "__main__":
    main()
