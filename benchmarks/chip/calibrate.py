#!/usr/bin/env python3
"""Readings behind each limit of `correct`, on the chip, at the cell's own
size: for each seed, one run of the cell (a short window is enough) that
also reads the fp8 control and, for training, half of each batch left out,
both planted in the reference put in the program's place, and judges each
by the cell's committed limits.  All seeds run in one process, so the
programs compile once.  The benchmark's runs never call this.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 8

Prints one JSON line per seed: {"seed", "correct", "readings", "control",
...}, where each control carries its readings and its own `correct`.
Exits 1 if a run is not correct or the control comes out correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench_harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, args.workload)
    traffic = H.load_traffic(cell["traffic"])
    devices = H.require_chip(cell["chips"])
    H.use_compile_cache()
    runner = H.load_runner(traffic["kind"])
    bad = False
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = runner.run(H.RunArgs(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=False,
            config=H.load_config(spec, cell["config"]),
            traffic=traffic, limits=H.load_checks(args.workload),
            reference=H.load_reference(spec, cell["config"]), devices=devices,
            t_start=t0, control=True))
        out = {"seed": seed, "correct": r.correct, "readings": r.ctx["readings"],
               "metrics": r.metrics, "device": r.device,
               "seconds": time.perf_counter() - t0}
        for k in ("control", "half_batch"):
            if k in r.ctx:
                out[k] = r.ctx[k]
        print(json.dumps(out, default=float), flush=True)
        bad = bad or not r.correct or r.ctx["control"]["correct"]
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
