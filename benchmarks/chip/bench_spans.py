#!/usr/bin/env python3
"""The program's own spans (`repro.obs`) in the chip benchmark: the
per-layer numbers they give, and device idle gaps named by them.

Two views of the same spans:

* the records that `obs.drain()` returns, on the host's `perf_counter`
  clock, the clock of the benchmark's own `bench.*` spans
  (`bench_harness.Spans`).  `program_ctx` reduces them, inside the windows
  those spans mark, to the keys that the readers in `layer_metrics/`
  (`ckpt_snapshot_s`, `ckpt_write_s`, `ckpt_write_fs_share`,
  `ckpt_restore_fs_share`, `fs_read_rpc_us`, `decode_host_ms`) return;
* their `TraceAnnotation`s in the profiler trace, on the device's clock.
  `idle_by_program_span` names the device's idle time in the window by the
  program span the host was in: each idle gap of the first chip is cut at
  every program span's start and end inside it, and each piece is named,
  as `bench_trace.reduce_events` names a gap, by the innermost span (the
  shortest, on any host thread) that holds the piece's midpoint, or "none".
  A gap in which no span starts or ends gets one name, as there.

Run as a script, it runs one cell as `run.py` does, with the program's
tracer on from set-up to the end of the checks, and prints one JSON line:
the end-to-end metrics (`--trace 0`, to weigh the tracer's cost against
`run.py`'s untraced runs) or the per-layer ones (`--trace 1`), and the six
metrics above, the gap attribution and the slowest decode step:

    python3 benchmarks/chip/bench_spans.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--slice-out FILE]

`--slice-out` writes the traced window's last checkpoint save, device ops
and program spans, in the form of `testdata/*.trace.json`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench_trace  # noqa: E402

PROGRAM_PREFIXES = ("train.", "ckpt.", "data.", "fs.", "serve.")
METRICS = ("ckpt_snapshot_s", "ckpt_write_s", "ckpt_write_fs_share",
           "ckpt_restore_fs_share", "fs_read_rpc_us", "decode_host_ms")
Window = Tuple[float, float]          # perf_counter seconds


# ---------------------------------------------------------------------------
# records -> ctx keys
# ---------------------------------------------------------------------------

def _s(ns: int) -> float:
    return ns / 1e9


def _in(r, w: Window) -> bool:
    return w[0] <= _s(r.start_ns) <= w[1]


def _dur(r) -> float:
    return (r.end_ns - r.start_ns) / 1e9


def _under(records, roots: List[Any], prefix: str) -> float:
    """Seconds of the spans named `prefix`... that descend from `roots`,
    counting none that descends from another such span."""
    by_id = {r.id: r for r in records}
    root_ids = {r.id for r in roots}
    total = 0.0
    for r in records:
        if not r.name.startswith(prefix):
            continue
        p = r.parent
        while p is not None and p not in root_ids:
            q = by_id.get(p)
            if q is None or q.name.startswith(prefix):
                p = None
                break
            p = q.parent
        if p is not None:
            total += _dur(r)
    return total


def window_of(bench_records, name: str) -> Optional[Window]:
    """The (start, end) of the benchmark span `name` (the last, if several)."""
    found = [(t0, t1) for n, t0, t1 in bench_records if n == name]
    return found[-1] if found else None


def program_ctx(records, windows: Dict[str, Window],
                rpc: Optional[Tuple[Dict[str, Any], Dict[str, Any]]] = None
                ) -> Dict[str, Any]:
    """ctx keys from the tracer's records.  `windows` holds the benchmark's
    `bench.window` (and for training `bench.restore`) spans; `rpc` the
    training window's `RpcStats.snapshot()` before and after."""
    out: Dict[str, Any] = {}
    win = windows.get("bench.window")
    if win is not None:
        snaps = [r for r in records if r.name == "ckpt.snapshot" and _in(r, win)]
        if snaps:
            out["ckpt_snapshot_s"] = sum(_dur(r) for r in snaps)
        writes = [r for r in records if r.name == "ckpt.write" and _in(r, win)]
        if writes:
            busy = sum(_dur(r) for r in writes)
            out["ckpt_write_s"] = busy / len(writes)
            out["ckpt_write_fs_share"] = 100.0 * _under(records, writes,
                                                        "fs.") / busy
        steps = [r for r in records if r.name == "serve.decode_step"
                 and _in(r, win)]
        if steps:
            ids = {r.id for r in steps}
            sync: Dict[int, float] = defaultdict(float)
            for r in records:
                if r.name == "serve.token_sync" and r.parent in ids:
                    sync[r.parent] += _dur(r)
            out["decode_host_ms"] = 1e3 * sum(
                _dur(r) - sync[r.id] for r in steps) / len(steps)
    rwin = windows.get("bench.restore")
    if rwin is not None:
        restores = [r for r in records if r.name == "ckpt.restore"
                    and _in(r, rwin)]
        busy = sum(_dur(r) for r in restores)
        if busy > 0:
            out["ckpt_restore_fs_share"] = 100.0 * _under(records, restores,
                                                          "fs.") / busy
    if rpc is not None:
        before, after = rpc
        n = after["by_type"].get("READ", 0) - before["by_type"].get("READ", 0)
        if n > 0:
            w = (after["wait_ns"].get("READ", 0)
                 - before["wait_ns"].get("READ", 0))
            out["fs_read_rpc_us"] = w / n / 1e3
    return out


# ---------------------------------------------------------------------------
# profiler trace -> idle time named by program spans
# ---------------------------------------------------------------------------

def load_program_events(xplane: Path) -> List[Dict]:
    """The host events of program spans in one `.xplane.pb`, in the form of
    `bench_trace.load_events`' events (line = host thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    out: List[Dict] = []
    for plane in pd.planes:
        if bench_trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIXES):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


def idle_gaps(events: List[Dict]) -> Tuple[Window, List[Window]]:
    """The `bench.window` span and the first chip's idle gaps inside it
    (trace nanoseconds), as `bench_trace.reduce_events` finds them."""
    windows = [e for e in events if e["name"] == bench_trace.WINDOW_SPAN]
    if not windows:
        raise ValueError("trace has no bench.window span")
    w = max(windows, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    planes = sorted({e["plane"] for e in events
                     if bench_trace.DEVICE_PLANE.match(e["plane"])})
    if not planes:
        raise ValueError("trace has no TPU device plane")
    busy = bench_trace._clip(bench_trace._union(
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
        if e["plane"] == planes[0] and e["line"] == bench_trace.OP_LINE),
        lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return (lo, hi), gaps


def idle_by_program_span(events: List[Dict]) -> Dict[str, Any]:
    """{"idle_s", "by_span": [[name, seconds, share %], ...]}: the window's
    device idle time by the innermost program span the host was in ("none"
    outside every one), largest first."""
    _, gaps = idle_gaps(events)
    spans = [e for e in events if e["name"].startswith(PROGRAM_PREFIXES)]
    # sweep: at equal times gaps and spans close before they open
    points = []
    for i, e in enumerate(spans):
        points.append((e["start_ns"], 1, i))
        points.append((e["start_ns"] + e["dur_ns"], 0, i))
    for s, e in gaps:
        points.append((s, 1, -1))
        points.append((e, 0, -1))
    points.sort()
    active: Dict[int, float] = {}
    in_gap = False
    prev = None
    by: Dict[str, float] = defaultdict(float)
    for t, opening, i in points:
        if in_gap and t > prev:
            name = (spans[min(active, key=active.get)]["name"] if active
                    else "none")
            by[name] += (t - prev) / 1e9
        prev = t
        if i < 0:
            in_gap = bool(opening)
        elif opening:
            active[i] = spans[i]["dur_ns"]
        else:
            active.pop(i, None)
    idle = sum(by.values())
    return {"idle_s": idle,
            "by_span": [[k, v, 100.0 * v / idle if idle else 0.0]
                        for k, v in sorted(by.items(), key=lambda kv: -kv[1])]}


def save_slice(events: List[Dict], path: Path) -> Dict[str, Any]:
    """The traced window's last `ckpt.save` to the window's end: its device
    ops and modules and the program spans, with a `bench.window` event over
    that interval, written in the form of `testdata/*.trace.json`.  The
    interval starts 5 ms before the save, or earlier at the start of the
    last device op that began before it, but not before the window."""
    (lo, hi), _ = idle_gaps(events)
    saves = [e for e in events if e["name"] == "ckpt.save"
             and lo <= e["start_ns"] <= hi]
    if not saves:
        raise ValueError("no ckpt.save span in the window")
    t = max(e["start_ns"] for e in saves)
    a = max(lo, min(t - 5e6, max((e["start_ns"] for e in events
                                  if e["line"] == bench_trace.OP_LINE
                                  and bench_trace.DEVICE_PLANE.match(e["plane"])
                                  and e["start_ns"] < t), default=t)))
    keep = [dict(e) for e in events
            if e["name"] != bench_trace.WINDOW_SPAN
            and (bench_trace.DEVICE_PLANE.match(e["plane"])
                 or e["name"].startswith(PROGRAM_PREFIXES))
            and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > a]
    host = next(e["plane"] for e in events
                if e["name"] == bench_trace.WINDOW_SPAN)
    keep.append({"plane": host, "line": "python",
                 "name": bench_trace.WINDOW_SPAN, "start_ns": a,
                 "dur_ns": hi - a})
    for e in keep:
        if bench_trace.DEVICE_PLANE.match(e["plane"]) \
                and e["line"] == bench_trace.OP_LINE:
            e["name"] = bench_trace.op_label(e["name"])
    r = bench_trace.reduce_events(keep)
    rec = {"about": "mamba2-130m.train-ckpt on one TPU v5 lite chip: the "
                    "window's last checkpoint save (5 ms before its ckpt.save "
                    "span) to the window's end, device op and module lines "
                    "(op names cut to 96 characters) and the program's "
                    "spans; the bench.window event marks that interval",
           "expected": {"window_s": r["window_s"], "busy_s": r["busy_s"],
                        "programs": [[p["name"], p["program_id"], p["count"],
                                      p["device_s"]] for p in r["programs"]],
                        "idle_by_program_span": idle_by_program_span(keep)},
           "events": keep}
    path.write_text(json.dumps(rec))
    return rec


# ---------------------------------------------------------------------------
# one cell with the program's tracer on
# ---------------------------------------------------------------------------

def read_new_metrics(ctx: Dict[str, Any], device) -> Dict[str, float]:
    import bench_harness as H
    out = {}
    for name in METRICS:
        reader = H.load_module(HERE / "layer_metrics" / f"{name}.py")
        v = reader.read(ctx, device)
        if v is not None and math.isfinite(v):
            out[name] = float(v)
    return out


def run_traced(runner, args) -> Tuple[Any, List[Any], int]:
    """`runner.run(args)` with the program's tracer on, and the keys of
    `program_ctx` added to its ctx (the training window's `RpcStats` taken
    around `Trainer.run`).  Returns the result, the records and the count
    dropped."""
    from repro import obs
    from repro.launch import train

    rpc: List[Tuple[Dict, Dict]] = []
    run = train.Trainer.run

    def counted(self):
        before = self.agent.stats.snapshot()
        out = run(self)
        rpc.append((before, self.agent.stats.snapshot()))
        return out

    train.Trainer.run = counted
    obs.enable()
    try:
        result = runner.run(args)
    finally:
        obs.disable()
        train.Trainer.run = run
    records, dropped = obs.drain()
    windows = {n: w for n in ("bench.window", "bench.restore")
               if (w := window_of(args.spans.records, n)) is not None}
    result.ctx.update(program_ctx(records, windows, rpc[-1] if rpc else None))
    return result, records, dropped


def slowest_decode_step(records, win: Window) -> Optional[Dict[str, Any]]:
    steps = [r for r in records if r.name == "serve.decode_step" and _in(r, win)]
    if not steps:
        return None
    s = max(steps, key=_dur)
    inside = [r for r in records if r.id != s.id
              and r.start_ns < s.end_ns and r.end_ns > s.start_ns]
    return {"token": s.attrs.get("token"), "ms": 1e3 * _dur(s),
            "median_ms": 1e3 * sorted(map(_dur, steps))[len(steps) // 2],
            "spans": [[r.name, r.thread, 1e3 * _dur(r)] for r in inside]}


def main(argv=None) -> int:
    import bench_harness as H
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--slice-out", type=Path, default=None)
    a = ap.parse_args(argv)
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, a.workload)
    traffic = H.load_traffic(cell["traffic"])
    devices = H.require_chip(cell["chips"])
    H.use_compile_cache()
    args = H.RunArgs(workload=a.workload, seed=a.seed, seconds=a.seconds,
                     trace=bool(a.trace),
                     config=H.load_config(spec, cell["config"]),
                     traffic=traffic, limits=H.load_checks(a.workload),
                     reference=H.load_reference(spec, cell["config"]),
                     devices=devices, t_start=T_START)
    result, records, dropped = run_traced(H.load_runner(traffic["kind"]), args)
    line: Dict[str, Any] = {"correct": result.correct,
                            "device": result.device, "dropped": dropped,
                            "program": read_new_metrics(result.ctx,
                                                        result.device)}
    if a.trace:
        import run as bench_run
        line["metrics"] = {k: v["value"] for k, v in bench_run.layer_metrics(
            spec, a.workload, result).items()}
        events = bench_trace.load_events(bench_trace.newest_xplane(
            H.TRACE_DIR / a.workload))
        events += load_program_events(bench_trace.newest_xplane(
            H.TRACE_DIR / a.workload))
        line["breakdown"] = result.breakdown
        line["idle_by_program_span"] = idle_by_program_span(events)
        if a.slice_out is not None:
            rec = save_slice(events, a.slice_out)
            line["slice"] = {"events": len(rec["events"]),
                             "bytes": a.slice_out.stat().st_size}
    else:
        line["metrics"] = dict(result.metrics)
    win = window_of(args.spans.records, "bench.window")
    if win is not None:
        line["slowest_decode_step"] = slowest_decode_step(records, win)
    for k in ("compiles_in_window", "window_s"):
        H.log(f"{k}: {result.ctx.get(k)}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
