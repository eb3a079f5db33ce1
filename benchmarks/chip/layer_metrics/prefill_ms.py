"""Mean of `Server.generate`'s own `prefill_s` over the window's requests,
in milliseconds."""


def read(ctx, device):
    return ctx.get("prefill_ms")
