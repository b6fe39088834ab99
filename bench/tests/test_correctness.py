"""``correct`` holds for the program and fails for the control and for each
fault the cell can have, in full runs of the harness on the CPU at a tiny
size (the look for a chip skipped, the timed path broken underneath)."""

import jax
import numpy as np
import pytest

import control
import harness
import rehearse

DIM = 256
SEED = 2**33 + 11
CELL = "powit-n1-steady"


def _run(cell, seconds=0.4):
    result, _ = rehearse.rehearse(cell, SEED, seconds, DIM)
    return result


def test_program_is_correct():
    result = _run(CELL)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0.0 for c in result["checks"].values())
    assert result["attempted"] > 0 and result["failed"] == 0


def test_bf16_control_is_not_correct():
    checks = control.readings(CELL, SEED,
                              adjust=lambda c: rehearse.shrink(c, DIM))
    assert not all(v <= lim for _, v, lim in checks), checks


def _state_unchanged(monkeypatch):
    from repro.api.workload import MatVecPowerIteration

    original = MatVecPowerIteration.consume

    def consume(self, result, operand):
        original(self, result, operand)
        return operand

    monkeypatch.setattr(MatVecPowerIteration, "consume", consume)


def _half_rows_left_out(monkeypatch):
    from repro.api.workload import Workload

    def combine(self, partials):
        out = np.array(partials, copy=True)
        out[out.shape[0] // 2:] = 0
        return out

    monkeypatch.setattr(Workload, "combine", combine)


def _answer_altered(monkeypatch):
    from repro.kernels import ops

    def altered(kernel):
        def run(x, w, **kw):
            y = kernel(x, w, **kw)
            return y.at[0].add(jax.numpy.float32(1 / 256))
        return run

    for name in ("matvec", "matmat"):
        monkeypatch.setitem(ops._EXECUTOR_KERNELS, name,
                            altered(ops._EXECUTOR_KERNELS[name]))


@pytest.mark.parametrize("fault", [
    _state_unchanged, _half_rows_left_out, _answer_altered])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _run(CELL)
    assert not result["correct"], result["checks"]


def test_steps_missing_from_the_sample_are_not_correct():
    driver = harness.load_module("drivers", "iterative")
    ctx = harness.Ctx(name=CELL, seed=SEED, seconds=0.0, chips=1,
                      config={}, traffic={"check_steps": 3})
    st = {"ctx": ctx, "bits": 8, "x8": np.zeros((4, 4), np.int8),
          "reports": [None] * 5,
          "log": type("Log", (), {"sample": []})()}
    checks = {n: (v, lim) for n, v, lim in driver.check(st)}
    assert checks["unchecked_steps"] == (3.0, 0.0)
