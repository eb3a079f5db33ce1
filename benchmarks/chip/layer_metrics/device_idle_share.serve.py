"""Share of the serving window in which no operation ran on the device:
1 - busy / window, from the profiler trace (busy is the union of device op
intervals inside the `bench.window` span around the requests)."""


def read(ctx, device):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
