"""Operations and needed bytes of a stablelm-3b decode step, from the
configuration file's shapes (bench_flops: weights once, gathered embedding
rows, KV entries up to each row's position and the new entry's write)."""
import bench_flops


def decode_step(cfg, positions):
    return bench_flops.stablelm_decode_step(cfg, positions)
