"""Mean seconds of one background checkpoint write in the training window:
the program's `ckpt.write` span on the writer thread, from the first
`makedirs` to the MANIFEST commit and the old steps' removal
(bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("ckpt_write_s")
