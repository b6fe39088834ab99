"""Dormant-module wake-up: checkpoint round-trips and the decode demo.

``runtime/checkpoint.py`` is the fault-tolerance substrate the decentralized
re-planning story leans on (a job that survives a scheduler kill should
also survive a whole-process restart), and ``examples/decode_demo.py`` is
the batched prefill+decode driver (relocated from ``launch/serve.py``,
which stays as a deprecation shim). These tests pin the contracts:

- save/restore round-trips a pytree bitwise (including the bf16 widen/cast
  path and the JSON ``extra`` sidecar), the LATEST pointer tracks the
  newest step atomically, and shape mismatches fail loudly;
- a power-iteration run checkpointed mid-run and resumed in a FRESH engine
  finishes bitwise-equal to the uninterrupted run (the restart drill);
- ``decode_demo.main`` generates the expected (batch, gen_len) token grid
  on forced host devices, and the legacy ``repro.launch.serve`` import
  path still works — but warns.
"""

import os

import numpy as np
import pytest

from conftest import run_with_devices

from repro.runtime.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)


def test_checkpoint_roundtrip_bitwise_and_latest_pointer(tmp_path):
    d = str(tmp_path)
    tree = {
        "w": np.arange(12, dtype=np.float64).reshape(3, 4) / 7.0,
        "nested": {"b": np.array([1, 2, 3], dtype=np.int32)},
    }
    extra = {"note": "mid-run", "version": 3}
    p1 = save_checkpoint(d, 5, tree, extra)
    assert latest_checkpoint(d) == p1
    step, got, got_extra = restore_checkpoint(p1, tree)
    assert step == 5 and got_extra == extra
    assert np.asarray(got["w"]).tobytes() == tree["w"].tobytes()
    assert np.asarray(got["nested"]["b"]).tobytes() == \
        tree["nested"]["b"].tobytes()
    # A later save moves LATEST; the old checkpoint stays restorable.
    p2 = save_checkpoint(d, 9, tree)
    assert latest_checkpoint(d) == p2 and p2 != p1
    assert restore_checkpoint(p1, tree)[0] == 5


def test_checkpoint_bf16_widens_and_restores_dtype(tmp_path):
    import jax.numpy as jnp
    import ml_dtypes

    tree = {"p": jnp.linspace(0, 1, 8, dtype=jnp.bfloat16)}
    path = save_checkpoint(str(tmp_path), 0, tree)
    # On disk: widened float32 (npz cannot hold ml_dtypes)...
    import json
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["leaves"][0]["dtype"] == "bfloat16"
    raw = np.load(os.path.join(path, manifest["leaves"][0]["file"]))["value"]
    assert raw.dtype == np.float32
    # ... restored: cast back to the prototype's bf16, value-identical
    # (bf16 -> f32 is exact, so the round-trip loses nothing).
    _, got, _ = restore_checkpoint(path, tree)
    assert got["p"].dtype == ml_dtypes.bfloat16
    assert np.asarray(got["p"], dtype=np.float32).tobytes() == \
        np.asarray(tree["p"], dtype=np.float32).tobytes()


def test_checkpoint_shape_mismatch_and_missing_leaf_fail_loudly(tmp_path):
    tree = {"w": np.ones((2, 2))}
    path = save_checkpoint(str(tmp_path), 1, tree)
    with pytest.raises(ValueError, match="shape"):
        restore_checkpoint(path, {"w": np.ones((3, 3))})
    with pytest.raises(KeyError, match="missing leaf"):
        restore_checkpoint(path, {"other": np.ones((2, 2))})
    assert latest_checkpoint(str(tmp_path / "nowhere")) is None


def test_midrun_checkpoint_resume_bitwise(tmp_path):
    """The restart drill: run 9 steps; separately run 5, checkpoint the
    iterate, restore into a FRESH engine, run the remaining 4 — final
    eigvec and the resumed steps' residuals must be bitwise-equal."""
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
from repro.runtime import SyntheticSpeedClock, make_exact_matrix
from repro.runtime.checkpoint import (latest_checkpoint, restore_checkpoint,
                                      save_checkpoint)

BASE = [1000., 1400., 1900., 2600.]
X = make_exact_matrix(4 * 96, 0)
CKPT = %r

def engine():
    return ElasticEngine(
        MatVecPowerIteration(seed=0),
        Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, verify="exact",
                     initial_speeds=tuple(BASE)),
        backend="device", n_machines=4,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))

# Uninterrupted reference: 9 steps in one engine.
ref = engine().run(X, n_steps=9)

# Interrupted: 5 steps, checkpoint the operand, restart, 4 more steps.
eng1 = engine()
res1 = eng1.run(X, n_steps=5)
w_mid = res1.result.eigvec  # the normalized iterate the next step consumes
save_checkpoint(CKPT, 5, {"w": w_mid}, extra={"n_done": 5})

eng2 = engine()
step, tree, extra = restore_checkpoint(latest_checkpoint(CKPT),
                                       {"w": np.asarray(w_mid)})
assert step == 5 and extra["n_done"] == 5
res2 = eng2.run(X, n_steps=9 - step, operand=np.asarray(tree["w"]))

assert np.array_equal(res2.result.eigvec, ref.result.eigvec)
assert res2.result.residuals == ref.result.residuals[step:]
print("RESUME_OK")
""" % str(tmp_path / "ckpt"), n_devices=4)
    assert "RESUME_OK" in out


@pytest.mark.slow
def test_decode_demo_generates_token_grid():
    out = run_with_devices("""
import importlib.util, os
path = os.path.join(%r, "examples", "decode_demo.py")
spec = importlib.util.spec_from_file_location("decode_demo", path)
demo = importlib.util.module_from_spec(spec)
spec.loader.exec_module(demo)
gen = demo.main(["--arch", "mamba2-370m", "--reduced", "--batch", "2",
                 "--prompt-len", "8", "--gen-len", "3"])
assert gen.shape == (2, 3), gen.shape
assert (gen >= 0).all()
print("DECODE_OK", gen.shape)
""" % os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           n_devices=4)
    assert "DECODE_OK" in out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_launch_serve_shim_warns_and_delegates():
    """The legacy path still imports and still runs the demo — through a
    DeprecationWarning. (argparse exits with code 2 on the missing
    required --arch BEFORE any jax work, so this stays a fast test: it
    proves the shim warns and hands argv to the relocated main.)"""
    from repro.launch import serve

    with pytest.warns(DeprecationWarning, match="decode_demo"):
        with pytest.raises(SystemExit) as exc:
            serve.main([])
    assert exc.value.code == 2


# ---------------------------------------------------------------------- #
# Property tests: the checkpoint substrate and full-engine resume
# ---------------------------------------------------------------------- #
from _hypothesis_compat import given, strategies as st

_LEAF_DTYPES = ("float64", "float32", "bfloat16", "int32")


@given(
    outer=st.sampled_from(_LEAF_DTYPES),
    inner=st.sampled_from(_LEAF_DTYPES),
    on_device=st.booleans(),
    step=st.integers(min_value=0, max_value=10**9),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_checkpoint_roundtrip_property(outer, inner, on_device, step, seed):
    """Any nested tree of f64/f32/bf16/i32 leaves — host numpy or jax
    device arrays — round-trips bitwise through save/restore, whatever
    step it was stamped with. (bf16 widens to f32 on disk; f32 -> bf16
    is exact on the way back, so even that path loses nothing.)"""
    import shutil
    import tempfile

    import ml_dtypes

    rng = np.random.default_rng(seed)

    def leaf(dtype, shape):
        if dtype == "int32":
            a = rng.integers(-10**6, 10**6, size=shape, dtype=np.int32)
        elif dtype == "bfloat16":
            a = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
        else:
            a = rng.standard_normal(shape).astype(np.dtype(dtype))
        if on_device:
            import jax.numpy as jnp
            return jnp.asarray(a)
        return a

    tree = {
        "w": leaf(outer, (3, 4)),
        "nested": {"b": leaf(inner, (7,)), "deep": {"c": leaf(outer, (2,))}},
    }
    d = tempfile.mkdtemp()
    try:
        path = save_checkpoint(d, step, tree, extra={"stamp": step})
        got_step, got, extra = restore_checkpoint(path, tree)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    assert got_step == step and extra == {"stamp": step}
    pairs = [(tree["w"], got["w"]),
             (tree["nested"]["b"], got["nested"]["b"]),
             (tree["nested"]["deep"]["c"], got["nested"]["deep"]["c"])]
    for want, have in pairs:
        w_np, h_np = np.asarray(want), np.asarray(have)
        assert h_np.dtype == w_np.dtype
        assert h_np.tobytes() == w_np.tobytes()


_RESUME_CHECKED: set = set()


@given(
    fuse=st.sampled_from((1, 4)),
    cut=st.sampled_from((3, 5)),
)
def test_engine_save_state_resume_bitwise_property(fuse, cut):
    """The full-engine drill as a property over cut points: run `cut`
    steps, snapshot the COMPLETE resumable state (iterate, EWMA, plan
    cache keys, clock RNG, pending measurements), resume in a FRESH
    engine, finish — bitwise-equal to the uninterrupted run. cut=5 with
    fuse=4 lands mid-window (the resumed run re-tiles its windows);
    every (fuse, cut) pair is a mid-trace cut for the EWMA/plan state.
    Each example is a subprocess; the tiny domain keeps this tractable
    under both real hypothesis and the fallback sampler."""
    if (fuse, cut) in _RESUME_CHECKED:
        return
    _RESUME_CHECKED.add((fuse, cut))
    out = run_with_devices("""
import numpy as np
from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
from repro.runtime import SyntheticSpeedClock, make_exact_matrix
import tempfile

BASE = [1000., 1400., 1900., 2600.]
X = make_exact_matrix(4 * 96, 0)
FUSE, CUT = %d, %d

def engine():
    return ElasticEngine(
        MatVecPowerIteration(seed=0),
        Policy(placement="cyclic", replication=3, stragglers=1),
        EngineConfig(block_rows=16, verify="exact",
                     initial_speeds=tuple(BASE), fuse_steps=FUSE),
        backend="device", n_machines=4,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.1, seed=0))

clean = engine().run(X, n_steps=9)

e1 = engine()
e1.run(X, n_steps=CUT)
d = tempfile.mkdtemp()
e1.save_state(d)

e2 = engine()
step, w = e2.resume(d, data=X)
assert step == CUT, (step, CUT)
res = e2.run(n_steps=9 - CUT, operand=w)
assert np.array_equal(res.result.eigvec, clean.result.eigvec)
assert res.result.residuals == clean.result.residuals[CUT:]
print("RESUME_PROP_OK")
""" % (fuse, cut), n_devices=4)
    assert "RESUME_PROP_OK" in out
