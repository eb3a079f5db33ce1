"""Roofline share of the held experts' grouped products: per decode step of
the window, the larger of their FLOPs over the peak FLOP/s and their needed
bytes (the weights of the experts expected to be hit, the pairs' rows) over
the peak bandwidth (`moe_experts` of `configs/<config>_flops.py`), summed,
over the device time of the decode program's ops in the `moe.experts`
scope and its `ragged-dot` kernels (bench_scopes)."""
import bench_scopes


def read(ctx, device):
    return bench_scopes.roofline_share(
        ctx, device, "moe.experts",
        lambda counts, cfg, pos: counts.moe_experts(cfg, len(pos)))
