"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read, per device plane ``/device:TPU:<i>``:

- the ``XLA Ops`` line. An event's name is its HLO instruction
  (``%name = <shape> opcode(...)``). Control flow (``while``,
  ``conditional``, ``call``) contains other ops and is left out, so what
  remains are the leaf ops; **busy** is the union of their intervals
  inside the traced window.
- **product ops** are matched by what the op does: a ``custom-call`` (a
  Pallas kernel), a ``dot`` or ``convolution``, or a fusion whose name
  (XLA names a fusion after the ops fused into it) holds ``dot`` or
  ``convolution``. Their summed device time is the denominator of a
  kernel's roofline share.
- **collectives** are ``all-reduce``, ``all-gather``, ``reduce-scatter``,
  ``collective-permute``, ``all-to-all`` and their async halves.
- the ``XLA Modules`` line (one event per program execution), which tells
  device-side gaps (inside a program: loop control) from gaps between
  programs.

The traced window is the benchmark's own host span (``bench.trace`` by
default), on the host plane's lines, in the same nanosecond timebase as
the device events. Each idle gap between programs is labelled with the
innermost event of the host's ``python`` line that covers its middle,
which is the benchmark's span (``bench.engine_run``) when
the host was in the program's Python code, or a runtime event such as a
transfer when it waited on one.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*.*?\s(?P<op>[a-z][a-z0-9_-]*)\(")
CONTROL = {"while", "conditional", "call"}
COLLECTIVE = {"all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all"}


def parse_op(event_name: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event ('' if none)."""
    m = _HLO.match(event_name)
    if not m:
        return event_name.split(" ")[0].lstrip("%"), ""
    return m.group("name"), m.group("op")


def is_product(name: str, op: str) -> bool:
    if op in ("custom-call", "dot", "convolution"):
        return True
    return op == "fusion" and ("convolution" in name or "dot" in name)


def is_collective(op: str) -> bool:
    base = op[:-6] if op.endswith("-start") else (
        op[:-5] if op.endswith("-done") else op)
    return base in COLLECTIVE


def _runs(starts: np.ndarray, ends: np.ndarray):
    """Disjoint covered runs (run_starts, run_ends) of intervals."""
    if starts.size == 0:
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    # A new run starts where an interval begins after every earlier end.
    prev_end = np.concatenate(([-np.inf], e[:-1]))
    new = np.flatnonzero(s > prev_end)
    return s[new], np.append(e[new[1:] - 1], e[-1])


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by intervals [starts, ends)."""
    rs, re_ = _runs(starts, ends)
    return float(np.sum(re_ - rs))


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float
         ) -> Tuple[np.ndarray, np.ndarray]:
    """Uncovered stretches (gap_starts, gap_ends) of [lo, hi)."""
    rs, re_ = _runs(starts, ends)
    a = np.concatenate(([lo], re_))
    b = np.concatenate((rs, [hi]))
    a, b = np.clip(a, lo, hi), np.clip(b, lo, hi)
    keep = b > a
    return a[keep], b[keep]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def _innermost(events, t):
    """Name of the shortest event covering time t."""
    best = None
    for name, s, e in events:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def reduce(path: str, devices: Optional[Sequence[int]] = None,
           window_span: str = "bench.trace", top: int = 10) -> Dict:
    """Numbers of the traced window, per device and summed.

    ``devices`` picks the TPU planes by index (default: all). Times are in
    seconds. Raises ValueError when the trace has no such device plane or
    no window span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_lines = {}
    dev_planes = {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            dev_planes[idx] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines[line.name] = _events(line)
    window = [ev for evs in host_lines.values() for ev in evs
              if ev[0] == window_span]
    if not window:
        raise ValueError(f"no host span {window_span!r} in {path}")
    lo = min(s for _, s, _ in window)
    hi = max(e for _, _, e in window)
    pick = sorted(dev_planes) if devices is None else list(devices)
    missing = [i for i in pick if i not in dev_planes]
    if missing or not pick:
        raise ValueError(f"trace has no device plane for TPU {missing}")
    python = host_lines.get("python", [])

    per = []
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for i in pick:
        lines = {ln.name: ln for ln in dev_planes[i].lines}
        ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        mods = _events(lines["XLA Modules"]) if "XLA Modules" in lines \
            else []
        st, en, prod, coll = [], [], 0.0, 0.0
        for ev_name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            name, op = parse_op(ev_name)
            if op in CONTROL:
                continue
            st.append(s)
            en.append(e)
            d = (e - s) * 1e-9
            op_time[name] += d
            if is_product(name, op):
                prod += d
            elif is_collective(op):
                coll += d
        st_a, en_a = np.asarray(st), np.asarray(en)
        busy = union_length(st_a, en_a) * 1e-9
        ga, gb = gaps(st_a, en_a, lo, hi)
        mid = 0.5 * (ga + gb)
        # Programs on one device run one at a time: a sorted search finds
        # the execution (if any) that holds each gap.
        m_st = np.asarray([s for _, s, _ in mods])
        m_en = np.asarray([e for _, _, e in mods])
        m_name = [n.split("(")[0] for n, _, _ in mods]
        k = np.searchsorted(m_st, mid, side="right") - 1
        for g in range(mid.size):
            j = int(k[g])
            if j >= 0 and mid[g] < m_en[j]:
                label = f"device: inside {m_name[j]}"
            else:
                host = _innermost(python, mid[g])
                label = f"host: {host}" if host else "host: outside spans"
            gap_time[label] += (gb[g] - ga[g]) * 1e-9
        per.append({"device": i, "busy_s": busy, "product_s": prod,
                    "collective_s": coll})
    win = (hi - lo) * 1e-9
    return {
        "window_s": win,
        "devices": per,
        "busy_s": float(np.mean([d["busy_s"] for d in per])),
        "product_s": float(sum(d["product_s"] for d in per)),
        "collective_s": float(sum(d["collective_s"] for d in per)),
        "idle_share": [1.0 - d["busy_s"] / win for d in per],
        "device_ops": [[k, v] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
    }
