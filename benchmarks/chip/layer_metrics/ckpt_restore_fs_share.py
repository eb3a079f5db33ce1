"""Share of the window's `ckpt.restore` time spent inside BuffetFS calls
(`fs.*` spans below it); the rest is checksums, `np.load` and assembly
(bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("ckpt_restore_fs_share")
