"""Mean microseconds the client blocked on one READ RPC over the training
window: the change in the Trainer's `RpcStats.wait_ns["READ"]` over the
change in its READ count (bench_spans.program_ctx)."""


def read(ctx, device):
    return ctx.get("fs_read_rpc_us")
