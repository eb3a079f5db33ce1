"""Seconds the training window's saves spent copying the state from the
device to the host: the sum of the program's `ckpt.snapshot` spans inside
`CheckpointManager.save` over the window (bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("ckpt_snapshot_s")
