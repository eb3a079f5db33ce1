"""Mean milliseconds per window step that `Trainer.run` waited for its
batch: a host-clock span in the benchmark's proxy around the Trainer's
`pipeline` iterator."""


def read(ctx, device):
    return ctx.get("data_wait_ms")
