import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Tests may shrink the placeholder device pool:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, from the compiled artifact alone (no execution):
  * memory_analysis()  — per-device argument/temp bytes (proves it fits HBM)
  * cost_analysis()    — per-device HLO FLOPs / bytes accessed
  * collective bytes   — parsed from the partitioned HLO text, per op kind
  * the three roofline terms (see benchmarks/roofline.py for the report)

Usage:
  python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] --out results/dryrun
Each cell appends a JSON record to <out>/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------- #
# HLO collective accounting
# ---------------------------------------------------------------------- #
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_COLL_RE = re.compile(
    r"^\s*(?:%\S+|\S+)\s*=\s*(?:\()?\s*((?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?,?\s*)+)(?:\))?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_CPU_CONVERT_RE = re.compile(
    r"ROOT %convert[\w\.\-]* = f32\[([0-9,]+)\][^\n]*convert\(%param"
)


def cpu_bf16_inflation_bytes(hlo_text: str, min_bytes: int = 64 * 2 ** 20) -> int:
    """CPU-backend artifact accounting: XLA's float-normalization pass keeps
    persistent f32 copies of large bf16 buffers (the CPU has no native
    bf16), e.g. a +100%-sized f32 shadow of every decode KV cache. These
    copies cannot exist on the TPU target (bf16 is MXU-native), so the
    roofline reports both the raw CPU peak and the TPU-adjusted peak.

    Counts the f32 bytes of entry-level wrapped-convert fusions bf16->f32
    above ``min_bytes``.
    """
    total = 0
    for m in _CPU_CONVERT_RE.finditer(hlo_text):
        dims = [int(x) for x in m.group(1).split(",") if x]
        b = 4
        for d in dims:
            b *= d
        if b >= min_bytes and "bf16[" in hlo_text[max(0, m.start() - 200): m.start()]:
            total += b
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device operand bytes per collective kind, from partitioned HLO.

    The op's *result* shape is always printed; operand bytes are recovered
    per op semantics: all-reduce/all-to-all/collective-permute move ~result
    bytes, all-gather's operand is result/group, reduce-scatter's operand is
    result*group.
    """
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_blob, kind = m.group(1), m.group(2)
        line = hlo_text[m.start(): hlo_text.find("\n", m.start())]
        if f"{kind}-done" in line:
            continue
        shapes = _SHAPE_RE.findall(shapes_blob)
        if not shapes:
            continue
        total = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                g = len([x for x in gl.group(1).split(",") if x.strip() != ""])
        if kind == "all-gather":
            total = total // max(g, 1)
        elif kind == "reduce-scatter":
            total = total * g
        out[kind] = out.get(kind, 0) + total
    return out


# ---------------------------------------------------------------------- #
# Cell construction
# ---------------------------------------------------------------------- #
def _sds(shape, dtype, sharding=None):
    import jax


    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (fn, arg_specs) ready for fn.lower(*arg_specs)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import (
        cache_specs, cell_applicable, get_config, input_specs, micro_batch_size,
        shape_by_name,
    )
    from repro.core import compile_plan, cyclic_placement, solve_assignment
    from repro.launch import sharding as shr
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.optim import adamw
    from repro.runtime.trainstep import make_fsdp_train_step, make_usec_train_step

    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return None, None, {"skip_reason": why}
    if shape.kind == "train":
        import dataclasses

        # Sequence-parallel residual stream: REQUIRED for the fsdp-mode
        # >=100B archs (activation residency + grad-reshard costs), but
        # MEASURED WORSE for usec-mode archs (the per-layer seq<->TP
        # reshard collectives dominate; EXPERIMENTS.md §Perf iteration 3).
        # Respect explicit per-cell choices: "" = mode default, "none" = off.
        bax = (("pod", "data") if multi_pod else ("data",)) if cfg.train_mode == "fsdp" else ()
        ax = cfg.act_shard_axis or ("model" if cfg.train_mode == "fsdp" else "")
        if ax == "none":
            ax = ""
        cfg = dataclasses.replace(cfg, act_shard_axis=ax, act_batch_axes=bax)
    if shape.kind != "train" and cfg.train_mode == "dp":
        import dataclasses

        # pure-DP is a TRAINING choice; serving keeps TP param sharding
        # (replicated params would 16x the per-token HBM read at decode).
        cfg = dataclasses.replace(cfg, train_mode="usec")
    meta = {"train_mode": cfg.train_mode, "avg_trips": 1.0,
            "n_active_params": cfg.n_active_params(), "n_params": cfg.n_params(),
            "kind": shape.kind,
            "tokens_global": shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)}
    mesh = make_production_mesh(multi_pod=multi_pod)
    meta["_mesh"] = mesh
    bundle = build_model(cfg)
    dp = shr.dp_axes(mesh)
    n_workers = int(np.prod([mesh.shape[a] for a in dp]))

    params_shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pshard = shr.param_shardings(params_shapes, cfg, mesh)
    params_specs = jax.tree.map(
        lambda sh, sd: _sds(sh.shape, sh.dtype, sd), params_shapes, pshard
    )

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(adamw.init, params_shapes)
        zero1_axes = tuple(mesh.axis_names) if cfg.train_mode == "dp" else None
        oshard = shr.opt_shardings(pshard, mesh, params_shapes, axes=zero1_axes)
        opt_specs = jax.tree.map(
            lambda sh, sd: _sds(sh.shape, sh.dtype, sd), opt_shapes, oshard
        )
        lr = _sds((), jnp.float32)
        if cfg.train_mode in ("usec", "dp"):
            worker_axes = dp if cfg.train_mode == "usec" else tuple(mesh.axis_names)
            if cfg.train_mode == "dp":
                n_workers = int(np.prod([mesh.shape[a] for a in worker_axes]))
            # Tile layout: tiles are microbatches (J = 2 copies, S = 1).
            # G never exceeds the sample count (a 512-worker pod training a
            # 256-sample batch leaves half the workers idle rather than
            # inventing extra tiles).
            tile_samples = micro_batch_size(cfg, shape, n_workers)
            G = max(shape.global_batch // max(tile_samples, 1), n_workers)
            G = min(G, shape.global_batch)
            tile_samples = max(shape.global_batch // G, 1)
            placement = cyclic_placement(n_workers, G, 2)
            sol = solve_assignment(placement, np.ones(n_workers), stragglers=1,
                                   lexicographic=False)
            plan = compile_plan(placement, sol, rows_per_tile=1, stragglers=1)
            t_stage = max(len(z) for z in placement.storage_sets())
            b_max = int(plan.n_valid.max()) + 2
            from repro.configs.shapes import batch_schema

            schema = batch_schema(cfg, "train", tile_samples, shape.seq_len)
            wspec = P(worker_axes if len(worker_axes) > 1 else worker_axes[0])
            staged_specs = {
                k: _sds((n_workers, t_stage) + shp, dt,
                        NamedSharding(mesh, wspec))
                for k, (shp, dt) in schema.items()
            }
            plan_specs = (
                _sds((n_workers, b_max), jnp.int32, NamedSharding(mesh, wspec)),
                _sds((n_workers, b_max), jnp.float32, NamedSharding(mesh, wspec)),
                _sds((n_workers, 1), jnp.int32, NamedSharding(mesh, wspec)),
            )
            step = make_usec_train_step(
                bundle, mesh, t_stage, b_max, grad_shardings=pshard,
                reduced_grad_shardings=oshard["m"],
                worker_axes=worker_axes,
            )
            args = (params_specs, opt_specs, None, staged_specs, *plan_specs, lr)
            meta["avg_trips"] = G * 2.0 / n_workers  # G tiles x (1+S) / workers
            meta.update(G=G, tile_samples=tile_samples, t_stage=t_stage, b_max=b_max)
            return step, args, meta
        else:
            from repro.configs.shapes import batch_schema

            n_micro = max(shape.global_batch // max(
                micro_batch_size(cfg, shape, n_workers) * n_workers, 1), 1)
            schema = batch_schema(cfg, "train", shape.global_batch, shape.seq_len)
            bshard = shr.batch_shardings(
                {k: _sds(shp, dt) for k, (shp, dt) in schema.items()}, mesh
            )
            batch_specs = {
                k: _sds(shp, dt, bshard[k]) for k, (shp, dt) in schema.items()
            }
            w_spec = _sds((shape.global_batch,), jnp.float32,
                          NamedSharding(mesh, P(dp)))
            step = make_fsdp_train_step(
                bundle, mesh, n_micro=n_micro, grad_shardings=pshard
            )
            args = (params_specs, opt_specs, batch_specs, w_spec, lr)
            meta.update(n_micro=n_micro)
            return step, args, meta

    if shape.kind == "prefill":
        import jax.numpy as jnp

        specs_in = input_specs(cfg, shape)
        bshard = shr.batch_shardings(specs_in, mesh)
        batch_specs = {k: _sds(v.shape, v.dtype, bshard[k]) for k, v in specs_in.items()}
        b = shape.global_batch
        cshard_out = shr.cache_shardings(
            cache_specs(cfg, b, shape.seq_len), cfg, mesh
        )
        logit_shard = shr.guarded(mesh, (b, cfg.vocab_size), dp, "model")
        fn = jax.jit(bundle.prefill, out_shardings=(cshard_out, logit_shard))
        return fn, (params_specs, batch_specs), meta

    # decode
    import jax.numpy as jnp

    b = shape.global_batch
    cspecs = cache_specs(cfg, b, shape.seq_len)
    cshard = shr.cache_shardings(cspecs, cfg, mesh)
    cache_in = jax.tree.map(lambda sh, sd: _sds(sh.shape, sh.dtype, sd), cspecs, cshard)
    token = _sds((b, 1), jnp.int32, shr.guarded(mesh, (b, 1), dp))
    pos = _sds((), jnp.int32)
    logit_shard = shr.guarded(mesh, (b, cfg.vocab_size), dp, "model")
    fn = jax.jit(
        bundle.decode_step,
        out_shardings=(cshard, logit_shard),
        donate_argnums=(1,),  # the cache is updated in place
    )
    return fn, (params_specs, cache_in, token, pos), meta


# ---------------------------------------------------------------------- #
# Runner
# ---------------------------------------------------------------------- #
def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Optional[str]) -> Dict[str, Any]:
    import jax

    multi = mesh_kind == "multi"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": 512 if multi else 256,
    }
    t0 = time.time()
    fn, args, meta = build_cell(arch, shape_name, multi)
    if fn is None:
        rec["status"] = "skipped"
        rec["reason"] = meta["skip_reason"]
        _emit(rec, out_dir)
        return rec
    import jax

    mesh_ctx = meta.pop("_mesh", None)
    rec["meta"] = meta
    try:
        import contextlib

        ctx = jax.set_mesh(mesh_ctx) if mesh_ctx is not None else contextlib.nullcontext()
        with ctx:
            lowered = fn.lower(*args)  # None args are valid empty pytrees
            t1 = time.time()
            compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        from repro.launch import hlo_cost
        sc = hlo_cost.analyze(txt, default_trips=meta.get("avg_trips", 1.0))
        coll = {k: int(v) for k, v in sc.collectives.items()}
        # analytic MODEL_FLOPS (the 6ND convention; fwd-only paths use 2ND)
        n_act = meta["n_active_params"]
        toks = meta["tokens_global"]
        mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[meta["kind"]]
        model_flops_global = mult * n_act * toks
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            flops_per_device=float(sc.flops),
            bytes_per_device=float(sc.bytes),
            xla_flops_per_device=float(cost.get("flops", 0.0)),
            xla_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
            model_flops_global=float(model_flops_global),
            model_flops_per_device=float(model_flops_global / rec["devices"]),
            dynamic_whiles=int(sc.dynamic_whiles),
            collective_bytes_per_device=coll,
            collective_total=int(sum(coll.values())),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
                "peak_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
            },
        )
        peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        infl = cpu_bf16_inflation_bytes(txt)
        rec["memory"]["cpu_bf16_inflation_bytes"] = infl
        rec["memory"]["peak_bytes_tpu"] = peak - infl
        rec["hbm_fit"] = bool(peak < 16 * 1024 ** 3)
        rec["hbm_fit_tpu"] = bool(peak - infl < 16 * 1024 ** 3)
    except Exception as e:  # record the failure; the dry-run must be fixable
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _emit(rec, out_dir)
    return rec


def _emit(rec: Dict[str, Any], out_dir: Optional[str]):
    line = (
        f"[{rec['arch']} | {rec['shape']} | {rec['mesh']}] {rec['status']}"
    )
    if rec["status"] == "ok":
        m = rec["memory"]
        line += (
            f" compile={rec['compile_s']}s flops/dev={rec['flops_per_device']:.3e}"
            f" peak={m['peak_bytes']/2**30:.2f}GiB"
            f" (tpu {m.get('peak_bytes_tpu', m['peak_bytes'])/2**30:.2f})"
            f" coll={rec['collective_total']/2**20:.1f}MiB"
            f" fit={rec['hbm_fit']}/{rec.get('hbm_fit_tpu', rec['hbm_fit'])}"
        )
    elif rec["status"] == "skipped":
        line += f" ({rec['reason']})"
    else:
        line += f" {rec['error'][:200]}"
    print(line, flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        slug = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
        rec = dict(rec)
        rec.pop("traceback", None)
        with open(os.path.join(out_dir, slug), "w") as f:
            json.dump(rec, f, indent=1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    from repro.configs import LM_SHAPES, list_archs

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = list_archs()
        shapes = [s.name for s in LM_SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        archs = [args.arch]
        shapes = [args.shape]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(arch, shape, mk, args.out)
                failures += rec["status"] == "error"
    if failures:
        print(f"{failures} cell(s) FAILED", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
