"""Program spans under the profiler, read back from the trace it writes.

An :class:`ElasticEngine` run under ``jax.profiler.trace`` writes its own
host spans: ``usec.run`` around the call, one ``usec.step`` (stepwise and
first arrival) or ``usec.window`` (fused) per dispatch, named by its
``step_num``, the phases ``usec.plan``, ``usec.put``, ``usec.enqueue``,
``usec.wait``, ``usec.fetch`` and ``usec.collect`` inside it, and one
``usec.consume`` per engine step. On first arrival the host's include
refresh and winner gather are ``usec.combine`` inside ``usec.collect``,
timed with the fetch by ``StepReport.combine_s`` (0 elsewhere). Each mode
runs in a subprocess on four forced host devices, once traced and once
not, and the results must be bitwise equal.
"""

import json

import pytest

from conftest import run_with_devices

_RUN = """
import glob
import json
import tempfile

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.api import ElasticEngine, EngineConfig, MatVecPowerIteration, Policy
from repro.runtime import SyntheticSpeedClock, make_exact_matrix

N, K, ARRIVAL, STEPS, MODE = {n}, {k}, {arrival!r}, {steps}, {mode!r}
BASE = [1000., 1400., 1900., 2600.][:N]
X = make_exact_matrix(4 * 96, 0)


def run():
    policy = (Policy(placement="cyclic", replication=3, stragglers=1)
              if N > 1 else
              Policy(placement="cyclic", replication=1, stragglers=0))
    eng = ElasticEngine(
        MatVecPowerIteration(seed=0), policy,
        EngineConfig(block_rows=16, fuse_steps=K, arrival=ARRIVAL,
                     matmul_mode=MODE, initial_speeds=tuple(BASE)),
        backend="device", n_machines=N,
        clock=SyntheticSpeedClock(BASE, jitter_sigma=0.0, seed=0))
    res = eng.run(X, n_steps=STEPS)
    return eng, res


def spans_of(path):
    # Every usec.* span, per host thread line, as (name, start, end, args).
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {{k: v for k, v in e.stats}} if e.stats else {{}})
                   for e in line.events if e.name.startswith("usec.")]
            if evs:
                lines.append(evs)
    return lines


_, plain = run()
d = tempfile.mkdtemp()
with jax.profiler.trace(d):
    eng, traced = run()
path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
same = (np.array_equal(plain.result.eigvec, traced.result.eigvec)
        and plain.result.residuals == traced.result.residuals
        and [r.straggled for r in plain.reports]
        == [r.straggled for r in traced.reports])
print(json.dumps({{
    "same": bool(same),
    "lines": spans_of(path),
    "combine_s": [r.combine_s for r in plain.reports + traced.reports],
    "lowered": eng.runner.lowered_step_text().splitlines()[0],
}}))
"""


def _parents(spans):
    """Parent index of each span (None at the top): spans on one thread
    nest, so a sweep in start order with a stack finds them."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    parent, stack = [None] * len(spans), []
    for i in order:
        while stack and spans[stack[-1]][2] <= spans[i][1]:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


@pytest.mark.parametrize("n,k,arrival,program,mode,path", [
    pytest.param(1, 1, "barrier", "usec_step", None, "loop",
                 id="1-1-barrier-usec_step"),
    pytest.param(1, 4, "barrier", "usec_window", None, "loop",
                 id="1-4-barrier-usec_window"),
    pytest.param(4, 1, "first", "usec_partials", None, "loop",
                 id="4-1-first-usec_partials"),
    # Pallas kernels, as on a TPU (interpreted here): the one-column
    # block list streams.
    pytest.param(4, 1, "first", "usec_partials", "interpret", "stream",
                 id="4-1-first-usec_partials-stream"),
])
def test_engine_spans_nest_per_step(n, k, arrival, program, mode, path):
    steps = 6
    out = json.loads(run_with_devices(
        _RUN.format(n=n, k=k, arrival=arrival, steps=steps, mode=mode),
        n_devices=4).strip().splitlines()[-1])
    assert out["same"], "results differ with the profiler on"
    assert out["lowered"].startswith(f"module @jit_{program} ")
    # All program spans sit on the one thread that ran the engine.
    assert len(out["lines"]) == 1
    spans = [tuple(s) for s in out["lines"][0]]
    parent = _parents(spans)
    name = [s[0] for s in spans]
    runs = [i for i, nm in enumerate(name) if nm == "usec.run"]
    assert len(runs) == 1 and spans[runs[0]][3]["steps"] == steps
    assert parent[runs[0]] is None

    outer, other = (("usec.window", "usec.step") if k > 1
                    else ("usec.step", "usec.window"))
    tops = sorted((i for i, nm in enumerate(name) if nm == outer),
                  key=lambda i: spans[i][1])
    assert all(parent[i] == runs[0] for i in tops)
    assert other not in name
    if k > 1:
        # Windows of K steps, the last one flushed short.
        assert [spans[i][3]["step_num"] for i in tops] == [0, 4]
        assert [spans[i][3]["steps"] for i in tops] == [4, 2]
    else:
        assert [spans[i][3]["step_num"] for i in tops] == list(range(steps))

    phases = ["usec.plan", "usec.put", "usec.enqueue", "usec.wait",
              "usec.fetch", "usec.collect"]
    for top in tops:
        kids = sorted((i for i in range(len(spans)) if parent[i] == top),
                      key=lambda i: spans[i][1])
        seq = [name[i] for i in kids if name[i] != "usec.precompile"]
        waits = [i for i in kids if name[i] == "usec.wait"]
        # One wait per dispatched worker on the first-arrival path.
        assert len(waits) == n
        if arrival == "first":
            assert sorted(spans[i][3]["worker"] for i in waits) == \
                list(range(n))
        collapsed = [nm for j, nm in enumerate(seq)
                     if j == 0 or seq[j - 1] != nm]
        assert collapsed == phases, collapsed
    # Each dispatch names the block-list path its program compiled.
    enqueues = [i for i, nm in enumerate(name) if nm == "usec.enqueue"]
    assert enqueues and all(spans[i][3]["path"] == path for i in enqueues)
    combines = [i for i, nm in enumerate(name) if nm == "usec.combine"]
    if arrival == "first":
        # One combine per step, inside that step's collect.
        assert len(combines) == steps
        assert all(name[parent[i]] == "usec.collect"
                   and parent[parent[i]] in tops for i in combines)
        assert all(c > 0 for c in out["combine_s"])
    else:
        assert not combines
        assert all(c == 0 for c in out["combine_s"])
    pre = [i for i, nm in enumerate(name) if nm == "usec.precompile"]
    for i in pre:
        assert name[parent[i]] in ("usec.collect", "usec.window")

    consumes = [i for i, nm in enumerate(name) if nm == "usec.consume"]
    assert len(consumes) == steps
    assert all(parent[i] == runs[0] for i in consumes)
    # Each consume follows the dispatch that produced its step.
    ends = [spans[i][2] for i in tops]
    assert all(any(e <= spans[i][1] for e in ends) for i in consumes)
