"""The trace reduction, on a trace recorded on a v5e chip (power iteration
on a 1024x1024 operand, three steps, under a ``bench.window`` span)."""

import os

import numpy as np
import pytest

import harness

trace = harness.load_module("", "trace")

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e-powit-1024.xplane.pb")


def _plain_read(path, span):
    """The same numbers by a plain loop over every event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lo = hi = None
    ops = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/host:") and e.name == span:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return lo, hi, ops


def test_recorded_trace_numbers():
    got = trace.reduce(RECORDED, window_span="bench.window")
    lo, hi, ops = _plain_read(RECORDED, "bench.window")
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    kernel = sum(e - s for n, s, e in ops if "custom-call(" in n) * 1e-9
    assert kernel > 0
    assert got["product_s"] == pytest.approx(kernel)
    # 192 kernel launches: 3 steps of 64 blocks of 16 rows.
    assert sum("custom-call(" in n for n, _, _ in ops) == 192
    leaf = [(s, e) for n, s, e in ops if " while(" not in n]
    covered = np.zeros(int(hi - lo) + 1, bool)
    for s, e in leaf:
        covered[int(max(s, lo) - lo):int(min(e, hi) - lo)] = True
    assert got["busy_s"] == pytest.approx(covered.sum() * 1e-9, rel=1e-3)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["idle_share"][0] == pytest.approx(
        1 - got["busy_s"] / got["window_s"])
    assert got["collective_s"] == 0
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    labels = [k for k, _ in got["idle_gaps"]]
    assert "device: inside jit_body" in labels
    assert any(k.startswith("host: ") for k in labels)
    names = [k for k, _ in got["device_ops"]]
    assert "usec_matvec_padded.4" in names and len(names) <= 10


def test_missing_span_or_device_raises():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce(RECORDED, window_span="no.such.span")
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce(RECORDED, devices=[3], window_span="bench.window")


@pytest.mark.parametrize("text,name,op,product", [
    ("%usec_matvec_padded.4 = f32[16,1]{1,0} custom-call(f32[16,32768] %a)",
     "usec_matvec_padded.4", "custom-call", True),
    ("%convolution_add_fusion.2 = f32[8,8]{1,0} fusion(f32[8,8] %a)",
     "convolution_add_fusion.2", "fusion", True),
    ("%dot.3 = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)",
     "dot.3", "dot", True),
    ("%dynamic-slice_bitcast_fusion.2 = f32[16,32768]{1,0} fusion(%g)",
     "dynamic-slice_bitcast_fusion.2", "fusion", False),
    ("%all-reduce.1 = f32[32768]{0} all-reduce(f32[32768] %y)",
     "all-reduce.1", "all-reduce", False),
    ("%while.1 = (s32[], f32[4]) while((s32[], f32[4]) %t)",
     "while.1", "while", False),
])
def test_parse_and_classify(text, name, op, product):
    got_name, got_op = trace.parse_op(text)
    assert (got_name, got_op) == (name, op)
    assert trace.is_product(got_name, got_op) == product


def test_collective_ops():
    assert trace.is_collective("all-reduce")
    assert trace.is_collective("all-gather-start")
    assert trace.is_collective("collective-permute-done")
    assert not trace.is_collective("fusion")


def test_union_and_gaps():
    s = np.array([0.0, 2.0, 3.0, 10.0])
    e = np.array([1.0, 5.0, 4.0, 11.0])
    assert trace.union_length(s, e) == 5.0
    a, b = trace.gaps(s, e, -1.0, 12.0)
    assert list(zip(a.tolist(), b.tolist())) == [
        (-1.0, 0.0), (1.0, 2.0), (5.0, 10.0), (11.0, 12.0)]
    assert trace.union_length(np.zeros(0), np.zeros(0)) == 0.0
