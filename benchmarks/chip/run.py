#!/usr/bin/env python3
"""Chip benchmark of BuffetFS's ML client stack: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and per-layer metrics are
found by name from `BENCHMARK.json` (see bench_harness.py).  The run needs
as many TPU chips as the cell asks for and never falls back to the CPU.
With `--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics, read from a profiler trace of the window
and from the benchmark's own spans and the program's counters.

The last line of standard output is one JSON object:
    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}
and the last lines of standard error list each compared number beside its
limit.  Any failure exits non-zero with no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench_harness as H  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(spec, workload: str, result: H.RunResult):
    out = {}
    for m in H.metrics_for(spec, workload, "per_layer"):
        reader = H.load_module(H.HERE / "layer_metrics" / f"{m['name']}.py")
        value = reader.read(result.ctx, result.device)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, args.workload)
    config = H.load_config(spec, cell["config"])
    traffic = H.load_traffic(cell["traffic"])
    limits = H.load_checks(args.workload)
    reference = H.load_reference(spec, cell["config"])
    runner = H.load_runner(traffic["kind"])
    devices = H.require_chip(cell["chips"])
    cache = H.use_compile_cache()
    H.log(f"{args.workload} seed {args.seed} on {devices[0].device_kind}; "
          f"compile cache {cache}")
    run_args = H.RunArgs(workload=args.workload, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         config=config, traffic=traffic, limits=limits,
                         reference=reference, devices=devices, t_start=T_START)
    result = runner.run(run_args)

    if args.trace:
        metrics = layer_metrics(spec, args.workload, result)
    else:
        metrics = {}
        for m in H.metrics_for(spec, args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": float(result.metrics[m["name"]]),
                                  "unit": m["unit"]}
    for k in ("compiles_in_window", "window_s", "check_s"):
        if k in result.ctx:
            H.log(f"{k}: {result.ctx[k]}")
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": result.device}
    if result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in result.checks}
    for c in result.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except H.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
