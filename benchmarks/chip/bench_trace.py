"""Profiler trace capture and its reduction to the benchmark's numbers.

A traced run records the window with `jax.profiler` (Python tracer off, so
the host's own work is not slowed by it) and reduces the `.xplane.pb` to a
flat list of events:

    {"plane", "line", "name", "start_ns", "dur_ns", "program_id"}

keeping the device planes' op and module lines and the benchmark's own host
spans (`bench.*` TraceAnnotations).  `reduce_events` then gives, inside the
window that the `bench.window` span marks:

* busy_s: the union of the intervals in which a device op ran, averaged
  over the chips; idle share is 1 - busy_s / window_s;
* per program (XLA module name and program id): executions and device time;
* the device ops that took the most time, each by its self time (less the
  ops nested in it, as a `while` op's body is);
* the longest idle gaps, each named by the innermost benchmark span that the
  host was in at the gap's midpoint ("host: none" outside every span).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_ID_SUFFIX = re.compile(r"^(.*?)\((\d+)\)$")


def start(log_dir: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    log_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def newest_xplane(log_dir: Path) -> Path:
    found = sorted(glob.glob(str(log_dir / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load_events(xplane: Path) -> List[Dict]:
    """The events the reduction needs, from one `.xplane.pb`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    out: List[Dict] = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if device and line.name == MODULE_LINE:
                    pid = _stat(ev, "program_id")
                    rec["program_id"] = None if pid is None else int(pid)
                out.append(rec)
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _self_times(ops: List[Dict], lo: float, hi: float):
    """(name, seconds) of each op inside [lo, hi], less the time of the ops
    nested in it (a `while` op's event spans its whole loop body)."""
    ordered = sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    selfs = [0.0] * len(ordered)
    stack: List[int] = []
    for i, e in enumerate(ordered):
        s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        selfs[i] = max(t - s, 0.0)
        while stack and (ordered[stack[-1]]["start_ns"] + ordered[stack[-1]]["dur_ns"]
                         <= e["start_ns"]):
            stack.pop()
        if stack:
            selfs[stack[-1]] -= selfs[i]
        stack.append(i)
    return [(e["name"], sec / 1e9) for e, sec in zip(ordered, selfs) if sec > 0]


def op_label(name: str, width: int = 96) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name and the start of its shape."""
    return name if len(name) <= width else name[:width] + "..."


def module_base(name: str) -> Tuple[str, Optional[int]]:
    """`jit_train_step(123)` -> ("jit_train_step", 123)."""
    m = _ID_SUFFIX.match(name)
    return (m.group(1), int(m.group(2))) if m else (name, None)


def reduce_events(events: List[Dict], top: int = 10) -> Dict:
    windows = [e for e in events if e["name"] == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace has no bench.window span")
    w = max(windows, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    window_s = (hi - lo) / 1e9

    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])})
    busy_by_plane: Dict[str, List[Tuple[float, float]]] = {}
    ops_s: Dict[str, float] = defaultdict(float)
    programs: Dict[Tuple[str, Optional[int]], Dict] = {}
    for p in planes:
        ops = [e for e in events if e["plane"] == p and e["line"] == OP_LINE]
        busy_by_plane[p] = _clip(_union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                                        for e in ops), lo, hi)
        for name, sec in _self_times(ops, lo, hi):
            ops_s[op_label(name)] += sec
        for e in events:
            if e["plane"] != p or e["line"] != MODULE_LINE:
                continue
            if not (lo <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= hi):
                continue
            base, pid = module_base(e["name"])
            pid = e.get("program_id", pid) if e.get("program_id") is not None else pid
            rec = programs.setdefault((base, pid), {"name": base, "program_id": pid,
                                                   "count": 0, "device_s": 0.0})
            rec["count"] += 1
            rec["device_s"] += e["dur_ns"] / 1e9
    if not planes:
        raise ValueError("trace has no TPU device plane")
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy_by_plane.values()) \
        / len(planes) / 1e9

    # idle gaps of the first chip, named by what the host was doing
    busy0 = busy_by_plane[planes[0]]
    gaps, cur = [], lo
    for s, e in busy0:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [e for e in events if e["name"].startswith(SPAN_PREFIX)
             and e["name"] != WINDOW_SPAN]

    def host_label(t: float) -> str:
        inside = [e for e in spans
                  if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]]
        if not inside:
            return "host: none"
        return "host: " + min(inside, key=lambda e: e["dur_ns"])["name"]

    gap_by_label: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        gap_by_label[host_label((s + e) / 2)] += (e - s) / 1e9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "device_planes": len(planes),
        "programs": sorted(programs.values(), key=lambda r: -r["device_s"]),
        "device_ops": sorted(ops_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[host_label((s + e) / 2), (e - s) / 1e9] for s, e in longest],
        "idle_by_host": sorted(gap_by_label.items(), key=lambda kv: -kv[1]),
    }
