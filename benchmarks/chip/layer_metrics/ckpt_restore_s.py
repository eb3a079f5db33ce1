"""Seconds inside the window's `CheckpointManager.restore` call, the span
that the benchmark wraps around the Trainer's `ckpt.restore` before its
`init_or_restore()`."""


def read(ctx, device):
    return ctx.get("ckpt_restore_s")
