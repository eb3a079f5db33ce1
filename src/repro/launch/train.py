"""Fault-tolerant training driver.

Wires every substrate together: BuffetFS-served data pipeline (prefetch +
hedged reads), checkpoint/restart over BuffetFS (async, atomic), AdamW, and
the jitted train step on the default device (no mesh is built).  Designed
so a SIGKILL at any step loses at most `ckpt_every` steps of work and a
restart resumes exactly: the count of batches the step has consumed rides
in the checkpoint manifest.

CLI (reduced config by default; `--full` for the published widths;
`--trace-dir DIR` records the program's spans and a profiler trace):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import tempfile
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..ckpt import CheckpointManager
from ..configs import get_config
from ..core import BAgent, BLib, BuffetCluster
from ..data import BuffetDataset, DataPipeline, ShardedSampler
from ..optim import AdamWConfig
from ..runtime.steps import make_train_state, make_train_step_fn
from .compile_cache import use_compile_cache
from .trace_dir import traced


@dataclass
class TrainerConfig:
    arch: str = "stablelm-3b"
    reduced: bool = True
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 128
    lr: float = 3e-4
    ckpt_every: int = 20
    log_every: int = 10
    run_name: str = "run0"
    n_servers: int = 4
    hedge_delay_s: Optional[float] = None
    resume: bool = True
    data_dir: Optional[str] = None  # BuffetFS backing dir


class Trainer:
    """End-to-end trainer over a BuffetFS storage cluster."""

    def __init__(self, tc: TrainerConfig, *, cluster: Optional[BuffetCluster] = None,
                 corpus: Optional[list] = None) -> None:
        self.tc = tc
        cfg = get_config(tc.arch)
        self.cfg = cfg.reduced() if tc.reduced else cfg
        self.opt_cfg = AdamWConfig(lr=tc.lr, total_steps=tc.steps,
                                   warmup_steps=max(1, tc.steps // 20))

        root = tc.data_dir or tempfile.mkdtemp(prefix="buffetfs_train_")
        self.cluster = cluster or BuffetCluster(root_dir=root,
                                                n_servers=tc.n_servers)
        self.agent = BAgent(self.cluster)
        self.lib = BLib(self.agent)

        # corpus: synthesize one if not given (quickstart path)
        if corpus is None:
            rng = np.random.default_rng(0)
            n = max(tc.global_batch * 16, 128)
            corpus = [rng.integers(1, self.cfg.vocab_size,
                                   size=tc.seq_len + 1).astype(np.uint32)
                      for _ in range(n)]
        try:
            self.dataset = BuffetDataset(self.lib, name="train")
            _ = self.dataset.spec  # existing corpus?
        except OSError:
            self.dataset = BuffetDataset.build(
                self.lib, corpus, name="train",
                replicate=tc.hedge_delay_s is not None)

        self.sampler = ShardedSampler(n_samples=len(self.dataset),
                                      global_batch=tc.global_batch,
                                      dp_rank=0, dp_size=1)
        self.pipeline = DataPipeline(self.dataset, self.sampler,
                                     seq_len=tc.seq_len,
                                     hedge_delay_s=tc.hedge_delay_s)
        self.ckpt = CheckpointManager(self.lib, tc.run_name, parts=4,
                                      keep_last=2)
        self.step_fn = jax.jit(make_train_step_fn(self.cfg, self.opt_cfg),
                               donate_argnums=(0,))
        self.state: Optional[Dict[str, Any]] = None
        self.start_step = 0

    # ------------------------------------------------------------------
    def init_or_restore(self) -> None:
        with obs.span("train.fresh_state"):
            self.state = make_train_state(self.cfg, self.opt_cfg,
                                          jax.random.PRNGKey(0))
        if not self.tc.resume:
            return
        try:
            step, restored = self.ckpt.restore(like=self.state)
        except FileNotFoundError:  # no committed checkpoint
            print("[trainer] fresh start")
            return
        with obs.span("train.restore_put", step=step):
            self.state = jax.device_put(restored)
        man = self.ckpt.manifest(step)
        self.sampler.load_state_dict(man.extra["sampler"])
        self.start_step = int(man.extra["train_step"])
        print(f"[trainer] resumed from step {self.start_step}")

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        if self.state is None:
            self.init_or_restore()
        tc = self.tc
        it = iter(self.pipeline)
        last_loss = float("nan")
        losses: Dict[int, float] = {}
        # `step` counts the steps done before this one: it is the sampler
        # step of the batch the step consumes
        for step in range(self.start_step, tc.steps):
            with obs.span("train.batch_wait", step=step):
                batch = next(it)
            with obs.span("train.h2d", step=step):
                jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
            with obs.span("train.dispatch", step=step):
                self.state, metrics = self.step_fn(self.state, jbatch)
            if (step + 1) % tc.log_every == 0 or step == tc.steps - 1:
                with obs.span("train.loss_sync", step=step):
                    last_loss = losses[step + 1] = float(metrics["loss"])
                    print(f"[trainer] step {step+1}/{tc.steps} "
                          f"loss={last_loss:.4f} "
                          f"lr={float(metrics['lr']):.2e} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"(hedged={self.pipeline.stats.hedged})")
            if (step + 1) % tc.ckpt_every == 0 or step == tc.steps - 1:
                # async save: training continues while BuffetFS persists
                self.ckpt.save(step + 1, self.state, block=False, extra={
                    "train_step": step + 1,
                    "sampler": self.pipeline.state_dict(),
                    "arch": self.cfg.name,
                })
        self.ckpt.wait()
        self.pipeline.stop()
        rpc = self.agent.stats.snapshot()
        return {"final_loss": last_loss, "losses": losses, "steps": tc.steps,
                "critical_rpcs": rpc["critical_path"],
                "async_rpcs": rpc["async_offpath"]}

    def shutdown(self) -> None:
        self.pipeline.stop()
        self.agent.shutdown()
        self.cluster.shutdown()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--run", default="run0")
    ap.add_argument("--trace-dir", default=None,
                    help="record program spans and a profiler trace of the "
                         "job into this directory (spans.jsonl at exit)")
    args = ap.parse_args()
    use_compile_cache()
    tc = TrainerConfig(arch=args.arch, steps=args.steps,
                       global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                       reduced=args.reduced, data_dir=args.data_dir,
                       run_name=args.run)
    with traced(args.trace_dir):
        tr = Trainer(tc)
        out = tr.run()
    print(f"[trainer] done: {out}")
    tr.shutdown()


if __name__ == "__main__":
    main()
