"""Multi-device integration tests (subprocess with forced host devices):
USEC matvec executor exactness, uneven train step, gradient compression,
end-to-end elastic training, mini dry-run."""

import pytest

from conftest import run_with_devices


def test_matvec_executor_exact_under_drops():
    out = run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from repro.core import *
from repro.runtime.executor import stage_matrix, block_plan, make_matvec_executor
N, G, J, S = 6, 6, 3, 1
p = cyclic_placement(N, G, J)
s = np.array([1,2,4,8,16,32], float)
sol = solve_assignment(p, s, stragglers=S)
plan = compile_plan(p, sol, rows_per_tile=64, stragglers=S, speeds=s, row_align=16)
rng = np.random.default_rng(0)
X = rng.normal(size=(G*64, 48)).astype(np.float32)
w = rng.normal(size=(48,)).astype(np.float32)
st = stage_matrix(X, p, 64)
mesh = jax.make_mesh((6,), ("data",), devices=jax.devices()[:6],
                     axis_types=(jax.sharding.AxisType.Auto,))
ex = make_matvec_executor(mesh, "data", rows_total=G*64, block_rows=16)
for bad in [(), (5,), (0,), (3,)]:
    bp = block_plan(plan, st.slot_of, 16, stragglers=bad)
    with jax.set_mesh(mesh):
        y = ex(jnp.asarray(st.staged), jnp.asarray(bp.blk_slot), jnp.asarray(bp.blk_off),
               jnp.asarray(bp.blk_goff), jnp.asarray(bp.blk_include), jnp.asarray(bp.n_blocks), jnp.asarray(w))
    err = float(np.max(np.abs(np.asarray(y) - X @ w)))
    assert err < 1e-3, (bad, err)
print("EXEC-OK")
""", n_devices=6)
    assert "EXEC-OK" in out


def test_usec_train_matches_fsdp_single_worker():
    """With one worker, no redundancy and identical data, the uneven-loop
    step and the GSPMD step must produce the same loss."""
    out = run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.core import cyclic_placement, solve_assignment, compile_plan
from repro.data import TokenPipeline
from repro.runtime.trainstep import make_usec_train_step, make_fsdp_train_step
from repro.runtime.executor import block_plan
from repro.launch.mesh import make_worker_mesh
from repro.optim import adamw

cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=64, attn_chunk=64, loss_chunk=32,
                 param_dtype="float32")
bundle = build_model(cfg)
mesh = make_worker_mesh(1, 1)
p = cyclic_placement(1, 4, 1)
pipe = TokenPipeline(cfg, p, seq_len=16, tile_samples=2, seed=0)
sol = solve_assignment(p, np.ones(1), stragglers=0)
plan = compile_plan(p, sol, rows_per_tile=1, stragglers=0)
sb = pipe.staged_for_step(0)
bp = block_plan(plan, sb.slot_of, 1)
params = bundle.init(jax.random.PRNGKey(0))
copy = lambda t: jax.tree.map(jnp.array, t)
with jax.set_mesh(mesh):
    opt = adamw.init(params)
    ustep = make_usec_train_step(bundle, mesh, sb.arrays["tokens"].shape[1], bp.b_max)
    _, _, _, m1 = ustep(copy(params), copy(opt), None,
                        {k: jnp.asarray(v) for k, v in sb.arrays.items()},
                        jnp.asarray(bp.blk_slot), jnp.asarray(bp.blk_include),
                        jnp.asarray(bp.n_blocks)[:, None], jnp.float32(1e-3))
    fstep = make_fsdp_train_step(bundle, mesh, n_micro=4)
    gb = pipe.global_batch(0)
    _, _, m2 = fstep(copy(params), copy(opt), {"tokens": jnp.asarray(gb["tokens"])},
                     jnp.ones((8,), jnp.float32), jnp.float32(1e-3))
l1, l2 = float(m1["loss"]), float(m2["loss"])
assert abs(l1 - l2) / l2 < 1e-4, (l1, l2)
print("PARITY-OK", l1, l2)
""", n_devices=2)
    assert "PARITY-OK" in out


def test_usec_train_straggler_drop_keeps_loss_exact():
    """S=1 plans: dropping any one worker must leave the combined loss and
    gradients identical (redundant copies take over)."""
    out = run_with_devices("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.core import cyclic_placement, solve_assignment, compile_plan
from repro.data import TokenPipeline
from repro.runtime.trainstep import make_usec_train_step
from repro.runtime.executor import block_plan
from repro.launch.mesh import make_worker_mesh
from repro.optim import adamw

cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=64, attn_chunk=64, loss_chunk=32,
                 param_dtype="float32")
bundle = build_model(cfg)
N = 4
mesh = make_worker_mesh(N, 1)
p = cyclic_placement(N, 8, 2)
pipe = TokenPipeline(cfg, p, seq_len=16, tile_samples=1, seed=0)
sol = solve_assignment(p, np.ones(N), stragglers=1)
plan = compile_plan(p, sol, rows_per_tile=1, stragglers=1)
sb = pipe.staged_for_step(0)
params = bundle.init(jax.random.PRNGKey(0))
losses = []
copy = lambda t: jax.tree.map(jnp.array, t)
with jax.set_mesh(mesh):
    opt = adamw.init(params)
    step = make_usec_train_step(bundle, mesh, sb.arrays["tokens"].shape[1],
                                int(plan.n_valid.max()) + 1)
    for bad in [(), (0,), (1,), (2,), (3,)]:
        bp = block_plan(plan, sb.slot_of, 1, stragglers=bad,
                        b_max=int(plan.n_valid.max()) + 1)
        _, _, _, m = step(copy(params), copy(opt), None,
                          {k: jnp.asarray(v) for k, v in sb.arrays.items()},
                          jnp.asarray(bp.blk_slot), jnp.asarray(bp.blk_include),
                          jnp.asarray(bp.n_blocks)[:, None], jnp.float32(0.0))
        losses.append(float(m["loss"]))
spread = max(losses) - min(losses)
assert spread < 1e-5, losses
print("STRAGGLER-OK", losses[0])
""", n_devices=4)
    assert "STRAGGLER-OK" in out


def test_grad_compression_error_feedback():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.runtime import compression

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
params = {"w": jnp.zeros((8, 8))}
state = compression.init_state(params)

def reduce_fn(g, st):
    return compression.compress_decompress(g, st, "data")

f = jax.shard_map(reduce_fn, mesh=mesh, in_specs=(P("data"), P()),
                  out_specs=(P(), P()), axis_names={"data"}, check_vma=False)
rng = np.random.default_rng(0)
g_global = rng.normal(size=(4, 8, 8)).astype(np.float32) * 0.01
want = g_global.sum(0)
with jax.set_mesh(mesh):
    total_err = []
    st = state
    for it in range(8):
        red, st = jax.jit(f)({"w": jnp.asarray(g_global.reshape(32, 8))}, st)
        # shard_map over dim0 splits (32,8) into per-worker (8,8)
        got = np.asarray(red["w"])
        total_err.append(np.abs(got - want).max() / (np.abs(want).max()))
# quantization error bounded and not exploding (error feedback at work)
assert total_err[-1] < 0.2, total_err
print("COMPRESS-OK", round(total_err[-1], 4))
""", n_devices=4)
    assert "COMPRESS-OK" in out


def test_elastic_training_e2e_loss_decreases():
    out = run_with_devices("""
import sys
from repro.launch.train import main
loss = main(["--arch", "stablelm-1.6b", "--reduced", "--workers", "4",
             "--steps", "40", "--seq-len", "64", "--tile-samples", "2",
             "--straggler-tolerance", "1", "--drop-stragglers", "1",
             "--churn", "0.05", "--lr", "3e-3", "--log-every", "0"])
print("FINAL-LOSS", loss)
assert loss is not None and loss < 4.5, loss  # zipf unigram entropy ~4.2; init ~4.9
""", n_devices=4)
    assert "FINAL-LOSS" in out


def test_checkpoint_restart_resharding():
    """Save on a 4-worker run, restore onto 2 workers (elastic restart)."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.runtime import checkpoint as ckpt

mesh4 = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                   NamedSharding(mesh4, P("data", None)))
d = tempfile.mkdtemp()
ckpt.save_checkpoint(d, 3, {"x": x})
mesh2 = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2],
                      axis_types=(jax.sharding.AxisType.Auto,))
step, tree, _ = ckpt.restore_checkpoint(
    ckpt.latest_checkpoint(d), {"x": jnp.zeros((8, 8))},
    shardings={"x": NamedSharding(mesh2, P("data", None))})
assert step == 3
np.testing.assert_allclose(np.asarray(tree["x"]), np.arange(64.0).reshape(8, 8))
assert tree["x"].sharding.mesh.shape["data"] == 2
print("RESHARD-OK")
""", n_devices=4)
    assert "RESHARD-OK" in out


@pytest.mark.slow
def test_dryrun_mini_cell():
    """One full dry-run cell on the production mesh (256 host devices)."""
    out = run_with_devices("""
import os
os.environ.setdefault("REPRO_DRYRUN_DEVICES", "256")
from repro.launch.dryrun import run_cell
rec = run_cell("mamba2-370m", "long_500k", "single", None)
assert rec["status"] == "ok", rec
assert rec["hbm_fit_tpu"], rec["memory"]
print("DRYRUN-OK", rec["memory"]["peak_bytes"])
""", n_devices=256, timeout=560)
    assert "DRYRUN-OK" in out
