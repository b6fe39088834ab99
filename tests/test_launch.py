"""Entry-point plumbing: the device-count check, the compile-cache
location, and the benchmark harness's failure propagation."""

import os
import subprocess
import sys

import pytest

from repro.launch import hostdev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_devices_names_both_counts():
    import jax

    n = len(jax.devices())
    hostdev.require_devices(n)
    with pytest.raises(SystemExit) as e:
        hostdev.require_devices(n + 3)
    msg = str(e.value)
    assert f"{n + 3} workers need {n + 3} devices" in msg
    assert f"found {n} {jax.devices()[0].platform}" in msg


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert hostdev.enable_compile_cache() == "/elsewhere/cache"
    assert updates == []          # jax reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert hostdev.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def _bench_run(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    from benchmarks import run

    return run


@pytest.mark.parametrize("mode", ["smoke", "child"])
def test_bench_run_propagates_a_failed_device_child(monkeypatch, mode):
    run = _bench_run(monkeypatch)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    calls = []

    def fake_run(argv, **kw):
        calls.append(argv)
        return subprocess.CompletedProcess(argv, 1, "", "boom")

    monkeypatch.setattr(subprocess, "run", fake_run)
    if mode == "smoke":
        with pytest.raises(SystemExit) as e:
            run.main(["--smoke"])
        assert e.value.code == 1
        assert len(calls) == 1        # stops at the first failed child
    else:
        assert run._run_devices_subprocess("bench_faults.py", steps=1) \
            is False


def test_bench_run_refuses_children_after_touching_jax(monkeypatch):
    import jax  # noqa: F401 - the parent now holds the backend

    run = _bench_run(monkeypatch)
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: pytest.fail(
        "a child was started by a parent that imported jax"))
    with pytest.raises(RuntimeError, match="imported jax"):
        run._run_devices_subprocess("bench_engine.py", smoke=True)
