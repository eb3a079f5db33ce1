"""Operations of a mamba2-130m training step, from the configuration file's
shapes (bench_flops: SSD chunk terms and the tied LM head, forward and
backward, no recompute)."""
import bench_flops


def train_step_flops(cfg, batch: int, seq: int) -> float:
    return bench_flops.mamba2_train_step_flops(cfg, batch, seq)
