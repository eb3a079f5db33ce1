"""Share of the training window's `ckpt.write` time spent inside BuffetFS
calls (`fs.*` spans below it); the rest is serialization, checksums and
Python (bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("ckpt_write_fs_share")
