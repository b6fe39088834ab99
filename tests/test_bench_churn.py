"""The benchmark's elastic cell, ``powit-n4-churn``, rehearsed through its
harness on four forced host devices at a tiny size: the churn schedule,
``correct`` on the program, and faults its check must catch, among them
one the product check cannot see (a masked copy has the same bits as the
winning copy)."""

import json

import pytest

from conftest import run_with_devices

SEED = 2**33 + 5

# Runs the cell once through bench/rehearse.py; prints the result line and
# what the driver's state says about the window and the sample.
_REHEARSE = """
import json, sys
import numpy as np
sys.path.insert(0, "bench")
import harness, rehearse

seen = {{}}
load = harness.load_module


def load_module(sub, name):
    mod = load(sub, name)
    if name == "trace":
        # The CPU writes no device plane: no device numbers to read.
        mod.reduce = lambda path, devices=None: {{
            "window_s": 0.0, "devices": [], "busy_s": 0.0, "product_s": 0.0,
            "collective_s": 0.0, "idle_share": [], "device_ops": [],
            "idle_gaps": []}}
    if name == "elastic":
        check = mod.check

        def keep(st):
            seen["st"] = st
            return check(st)
        mod.check = keep
    return mod


harness.load_module = load_module
{fault}
result, _ = rehearse.rehearse("powit-n4-churn", {seed}, {seconds}, 256,
                              trace={trace})
st = seen["st"]
print(json.dumps({{
    "result": result,
    "events": len(st["event_replan_s"]),
    "sampled_members": sorted({{len(a) for _, _, a in st["log"].placed}}),
    "sampled_kinds": sorted(k for k, s in st["log"].strata.items()
                            if s["kept"]),
}}))
"""

# One loaded worker's partial arrives as zeros.
_ZERO_PARTIAL = """
from repro.runtime.elastic_runner import ElasticRunner
combine = ElasticRunner._winner_combine
def zeroed(self, parts, loaded, entry, include):
    return combine(self, [np.zeros_like(parts[0])] + list(parts[1:]),
                   loaded, entry, include)
ElasticRunner._winner_combine = zeroed
"""

# The realized straggler's copy of one block wins in place of the
# surviving holder's: the same bits, so only the winner check sees it.
# ``steal`` moves the win on include weights; the faults plant it in the
# timed path, in the combine's gather and in the include refresh it takes.
_STEAL = """
def steal(bp, include, workers):
    inc = include.copy()
    valid = bp.blk_seg_t >= 0
    for m in workers:
        b = int(np.flatnonzero(valid[m])[0])
        inc[(bp.blk_goff == bp.blk_goff[m, b]) & valid] = 0.0
        inc[m, b] = 1.0
    return inc
"""

_MASKED_WINS = _STEAL + """
from repro.runtime.elastic_runner import ElasticRunner
combine = ElasticRunner._winner_combine
def stolen(self, parts, loaded, entry, include):
    # The loaded worker that wins no block is the masked one.
    idle = [m for m in loaded if not (include[m] > 0).any()][:1]
    return combine(self, parts, loaded, entry,
                   steal(entry.block, include, idle))
ElasticRunner._winner_combine = stolen
"""

_MASKED_WINS_IN_INCLUDE = _STEAL + """
from repro.runtime import executor
refresh = executor.refresh_include
def stolen(bp, plan, stragglers=()):
    return steal(bp, refresh(bp, plan, stragglers), stragglers)
executor.refresh_include = stolen
"""


def _rehearse(fault="", seconds=1.0, trace=False):
    out = run_with_devices(
        _REHEARSE.format(fault=fault, seed=SEED, seconds=seconds,
                         trace=trace), n_devices=4)
    return json.loads(out.strip().splitlines()[-1])


def test_schedule_is_the_seeds_and_keeps_one_worker_away():
    out = run_with_devices("""
import itertools, json, sys
sys.path.insert(0, "bench")
import harness
drv = harness.load_module("drivers", "elastic")
take = lambda seed: list(itertools.islice(
    drv.schedule(seed, 4, first=range(4)), 200))
print(json.dumps([take(2**33 + 5), take(2**33 + 5), take(7)]))
""", n_devices=1)
    a, again, other = json.loads(out.strip().splitlines()[-1])
    assert a == again and a != other
    members, away = set(range(4)), []
    for i, (gone, back) in enumerate(a):
        # Calls alternate: a worker leaves, then the same worker returns.
        if i % 2 == 0:
            assert back == [] and len(gone) == 1 and gone[0] in members
            members -= set(gone)
            away.append(gone[0])
        else:
            assert gone == [] and back == [away[-1]]
            members |= set(back)
        assert len(members) >= 3
    assert away[:4] == [0, 1, 2, 3]          # warm-up: each worker once
    assert set(away[4:]) == {0, 1, 2, 3}     # then drawn from the seed


def test_rehearsal_is_correct_under_churn_and_reads_its_metrics():
    out = _rehearse(seconds=3.0, trace=True)
    result = out["result"]
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0.0 for c in result["checks"].values())
    assert set(result["checks"]) == {
        "product_gap_units", "iterate_gap_units", "unchecked_steps",
        "held_rows_gap", "coverage_gap", "winner_gap"}
    assert out["events"] >= 2
    assert out["sampled_members"] == [3, 4]
    assert out["sampled_kinds"] == ["away", "event", "full"]
    metrics = result["metrics"]
    for name in ("replan_ms_per_event", "combine_ms_per_step",
                 "host_ms_per_step"):
        assert metrics[name]["value"] > 0, metrics


@pytest.mark.parametrize("fault,caught", [
    (_ZERO_PARTIAL, ("product_gap_units",)),
    (_MASKED_WINS, ("winner_gap",)),
    (_MASKED_WINS_IN_INCLUDE, ("winner_gap",)),
], ids=["zero_partial", "masked_worker_wins", "masked_worker_wins_in_include"])
def test_fault_is_not_correct(fault, caught):
    result = _rehearse(fault)["result"]
    checks = {n: c["value"] for n, c in result["checks"].items()}
    assert not result["correct"], checks
    assert any(checks[n] > 0 for n in caught), checks
    if fault is not _ZERO_PARTIAL:
        assert checks["product_gap_units"] == 0.0, checks


def test_bf16_control_is_not_correct_on_this_cell():
    out = run_with_devices(f"""
import json, sys
sys.path.insert(0, "bench")
import control, rehearse
checks = control.readings("powit-n4-churn", {SEED},
                          adjust=lambda c: rehearse.shrink(c, 256))
print(json.dumps(checks))
""", n_devices=1)
    checks = {n: (v, lim) for n, v, lim in
              json.loads(out.strip().splitlines()[-1])}
    assert set(checks) >= {"product_gap_units", "iterate_gap_units",
                           "unchecked_steps"}
    assert any(v > lim for v, lim in checks.values()), checks


@pytest.mark.parametrize("seen,unchecked", [
    ({"full": 2, "away": 2}, 1.0),
    ({"full": 47, "away": 46, "event": 3}, 0.0),
], ids=["a_kind_never_ran", "fewer_events_than_the_kind_owes"])
def test_each_kind_of_step_is_due(seen, unchecked):
    """A kind the window never ran is unchecked; a kind it ran fewer
    times than its share (a traced window of three calls holds three
    event steps) owes only those."""
    out = run_with_devices(f"""
import json, sys
import numpy as np
sys.path.insert(0, "bench")
import harness
drv = harness.load_module("drivers", "elastic")
ctx = harness.Ctx(name="powit-n4-churn", seed=1, seconds=0.0, chips=4,
                  config={{"n_machines": 4, "dim": 4, "replication": 3,
                          "stragglers": 1}},
                  traffic={{"check_steps": 12}})
zero = np.zeros(4, np.float32)
seen = {seen!r}
strata = {{k: {{"seen": n, "kept": [None] * min(4, n)}}
          for k, n in seen.items()}}
log = type("Log", (), {{
    "size": 4, "strata": strata, "placed": [],
    "sample": [(zero, zero, zero)] * sum(min(4, n) for n in seen.values()),
}})()
st = {{"ctx": ctx, "bits": 8, "x8": np.zeros((4, 4), np.int8),
      "reports": [None] * sum(seen.values()), "log": log}}
print(json.dumps(drv.check(st)))
""", n_devices=1)
    checks = {n: (v, lim) for n, v, lim in
              json.loads(out.strip().splitlines()[-1])}
    assert checks["unchecked_steps"] == (unchecked, 0.0)
    assert checks["product_gap_units"] == (0.0, 0.0)
