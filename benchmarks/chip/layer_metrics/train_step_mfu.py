"""The train step program's share of the chip's peak: the model FLOPs of a
step (forward and backward from shapes, no recompute; the configuration's
`configs/<config>_flops.py`) over the step program's device time in the
trace times the published bf16 peak.  The step program is the XLA module
with the most device time among those that ran once per window step."""
import bench_harness as H
import bench_peaks


def read(ctx, device):
    t = ctx.get("trace")
    if not t:
        return None
    steps = ctx["steps"]
    progs = [p for p in t["programs"] if p["count"] == steps]
    if not progs:
        return None
    busy = max(p["device_s"] for p in progs)
    if busy <= 0:
        return None
    tr, cfg = ctx["traffic"], ctx["config"]
    counts = H.load_flops(H.benchmark_spec(), cfg["name"])
    flops = counts.train_step_flops(cfg, tr["global_batch"], tr["seq_len"]) * steps
    peak = bench_peaks.peaks_for(device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (busy * peak)
