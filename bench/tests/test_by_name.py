"""A configuration, a traffic mix, a driver and a per-layer metric are
added with files alone: in a copy of the benchmark, the dummy files of
``tests/dummy`` go where the harness looks for them by name, entries are
appended to BENCHMARK.json, and the new cell runs without an edit to any
file the benchmark already had."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DUMMY = os.path.join(BENCH, "tests", "dummy")

RUN = """
import json, sys, time
sys.path.insert(0, {bench!r})
import harness
result, _ = harness.run_cell("dummy-cell", 2**40 + 3, 0.1, {trace},
                             t_start=time.perf_counter(), require_chip=False,
                             peaks={{}})
print(json.dumps(result))
"""


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _add_dummy(root):
    bench = root / "bench"
    shutil.copy(os.path.join(DUMMY, "dummy-rows.json"), bench / "configs")
    shutil.copy(os.path.join(DUMMY, "dummy-burst.json"), bench / "traffic")
    shutil.copy(os.path.join(DUMMY, "dummy_rowsum.py"), bench / "drivers")
    shutil.copy(os.path.join(DUMMY, "dummy_sums.py"), bench / "metrics")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "dummy-rows", "source": "a test", "why": "a test",
        "file": "bench/configs/dummy-rows.json", "reduced": []})
    spec["workloads"].append({
        "name": "dummy-cell", "config": "dummy-rows",
        "traffic": "dummy-burst", "chips": 1, "why": "a test"})
    spec["end_to_end"].append({
        "name": "sums_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["dummy-cell"]})
    spec["per_layer"].append({
        "name": "dummy_sums", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "sums_per_s",
        "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _run(root, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         RUN.format(bench=str(root / "bench"), trace=trace)],
        capture_output=True, text=True, env=env, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_new_cell_by_files_alone(tmp_path):
    root = _copy(tmp_path)
    before = _digests(root / "bench")
    _add_dummy(root)
    after = _digests(root / "bench")
    assert all(after[k] == v for k, v in before.items())
    assert set(after) - set(before) == {
        "configs/dummy-rows.json", "traffic/dummy-burst.json",
        "drivers/dummy_rowsum.py", "metrics/dummy_sums.py"}

    e2e = _run(root, trace=False)
    assert e2e["correct"] is True
    assert set(e2e["metrics"]) == {"sums_per_s", "setup_s"}
    assert e2e["checks"] == {"row_sum_gap": {"value": 0.0, "limit": 0.0}}
    assert list(e2e)[-1] == "checks"


def test_existing_cells_keep_their_metrics(tmp_path):
    root = _copy(tmp_path)
    _add_dummy(root)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "harness_copy", root / "bench" / "harness.py")
    h = importlib.util.module_from_spec(spec)
    sys.modules["harness_copy"] = h
    try:
        spec.loader.exec_module(h)
        cell = h.load_cell("powit-n1-steady")
    finally:
        del sys.modules["harness_copy"]
    assert [m["name"] for m in cell["end_to_end"]] == ["step_ms", "setup_s"]
    assert "dummy_sums" not in [m["name"] for m in cell["per_layer"]]
