"""The decode step program's roofline share: for each decode step, the
larger of its FLOPs over the peak FLOP/s and its needed bytes over the peak
bandwidth (the configuration's `configs/<config>_flops.py`), summed, over
the decode program's device time in the trace.  The decode program is the
XLA module with the most device time among those that ran once per decode
call of the window."""
import bench_harness as H
import bench_peaks


def read(ctx, device):
    t = ctx.get("trace")
    if not t:
        return None
    progs = [p for p in t["programs"] if p["count"] == ctx["decode_steps"]]
    if not progs:
        return None
    busy = max(p["device_s"] for p in progs)
    if busy <= 0:
        return None
    pk = bench_peaks.peaks_for(device["kind"])
    counts = H.load_flops(H.benchmark_spec(), ctx["config"]["name"])
    need = 0.0
    for prompt, n in ctx["decode_positions"]:
        for i in range(n):
            c = counts.decode_step(ctx["config"], [prompt + i] * ctx["batch"])
            need += max(c["flops"] / pk["bf16_flops_per_s"],
                        c["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * need / busy
