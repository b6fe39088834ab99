"""What a trace of the instrumented program holds, on a trace recorded on a
v5e chip: power iteration on a 1024x1024 operand, stepwise, one worker,
three steps in one ``engine.run`` under the benchmark's ``bench.trace``
and ``bench.engine_run`` spans, the harness's profiler options, launched
as ``python3``.

The program's spans (``usec.*``) sit on the thread line that holds the
window span; that line is named after the launcher (here ``python3``), so
a reader must find it by the window span, not by name. Every stretch in
which the device idles between programs lies inside one of those spans.
The accepted reduction reads the trace's device numbers unchanged.
"""

import os
import warnings

import numpy as np
import pytest

import harness

trace = harness.load_module("", "trace")

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e-powit-1024-spans.xplane.pb")
PHASES = ["usec.plan", "usec.put", "usec.enqueue", "usec.wait",
          "usec.fetch", "usec.collect"]


def _args(event):
    """A span's arguments. Reading an event's stats makes jaxlib warn that
    its stats type has no ``__module__`` (a DeprecationWarning, which this
    repo's test settings turn into an error raised inside jaxlib, where it
    aborts the process), so the warning is silenced around the read."""
    if not event.name.startswith(("usec.", "bench.")):
        return {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in event.stats}


def _read(path):
    """Host lines holding the window span, as (line name, events with
    their arguments); the chip's ``XLA Ops`` and ``XLA Modules`` events."""
    from jax.profiler import ProfileData

    window_lines, ops, mods = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    _args(e)) for e in line.events]
            if plane.name.startswith("/host:") and any(
                    ev[0] == "bench.trace" for ev in evs):
                window_lines.append((line.name, evs))
            if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                ops = evs
            if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                mods = evs
    return window_lines, ops, mods


@pytest.fixture(scope="module")
def recorded():
    return _read(RECORDED)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_sit_on_the_window_line(recorded):
    lines, _, _ = recorded
    assert [name for name, _ in lines] == ["python3"]
    evs = lines[0][1]
    window = next(ev for ev in evs if ev[0] == "bench.trace")
    spans = [ev for ev in evs if ev[0].startswith("usec.")]
    assert spans and all(_inside(s, window) for s in spans)
    runs = [s for s in spans if s[0] == "usec.run"]
    assert len(runs) == 1 and runs[0][3]["steps"] == 3
    steps = sorted((s for s in spans if s[0] == "usec.step"),
                   key=lambda s: s[1])
    nums = [s[3]["step_num"] for s in steps]
    assert nums == list(range(nums[0], nums[0] + 3))
    for step in steps:
        kids = sorted((s for s in spans if s[0] in PHASES
                       and _inside(s, step)), key=lambda s: s[1])
        assert [s[0] for s in kids] == PHASES
    consumes = [s for s in spans if s[0] == "usec.consume"]
    assert len(consumes) == 3
    assert not any(_inside(c, s) for c in consumes for s in steps)


def test_program_and_kernel_names(recorded):
    _, ops, mods = recorded
    assert len(mods) == 3
    assert {m[0].split("(")[0] for m in mods} == {"jit_usec_step"}
    kernels = [n for n, _, _, _ in ops if "custom-call(" in n]
    # 3 steps of 64 blocks of 16 rows, one launch each.
    assert len(kernels) == 192
    assert all(trace.parse_op(n)[0].startswith("usec_matvec.")
               for n in kernels)


def test_idle_between_programs_falls_in_spans(recorded):
    lines, ops, mods = recorded
    evs = lines[0][1]
    window = next(ev for ev in evs if ev[0] == "bench.trace")
    lo, hi = window[1], window[2]
    leaf = [(max(s, lo), min(e, hi)) for n, s, e, _ in ops
            if trace.parse_op(n)[1] not in trace.CONTROL
            and min(e, hi) > max(s, lo)]
    ga, gb = trace.gaps(np.array([s for s, _ in leaf], float),
                        np.array([e for _, e in leaf], float), lo, hi)
    spans = [ev for ev in evs if ev[0].startswith(("usec.", "bench."))]
    between = in_usec = 0.0
    for a, b in zip(ga, gb):
        mid = 0.5 * (a + b)
        if any(s <= mid < e for _, s, e, _ in mods):
            continue  # inside a program: loop control
        covering = [s for s in spans if s[1] <= mid < s[2]]
        assert covering, (a, b)
        innermost = min(covering, key=lambda s: s[2] - s[1])
        between += b - a
        in_usec += (b - a) * innermost[0].startswith("usec.")
    assert between > 0
    assert in_usec / between >= 0.9


def test_accepted_reduction_reads_the_device(recorded):
    _, ops, _ = recorded
    got = trace.reduce(RECORDED)
    kernel = sum(e - s for n, s, e, _ in ops if "custom-call(" in n) * 1e-9
    assert got["product_s"] == pytest.approx(kernel)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["collective_s"] == 0
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    assert "device: inside jit_usec_step" in [k for k, _ in got["idle_gaps"]]
