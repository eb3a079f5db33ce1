"""Roofline share of decode's latent attention: per decode step of the
window, the larger of its FLOPs over the peak FLOP/s and its needed bytes
(latent and rope entries up to each row's position) over the peak
bandwidth (`mla_decode_attn` of `configs/<config>_flops.py`), summed, over
the device time of the decode program's ops in the `mla.attend` scope
(bench_scopes)."""
import bench_scopes


def read(ctx, device):
    return bench_scopes.roofline_share(
        ctx, device, "mla.attend",
        lambda counts, cfg, pos: counts.mla_decode_attn(cfg, pos))
