"""Mean host milliseconds of one decode step in the serving window: each
`serve.decode_step` span less its `serve.token_sync` child, the wait for
the device's previous token (bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("decode_host_ms")
