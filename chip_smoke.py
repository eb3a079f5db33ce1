"""Bring-up check: BuffetFS's ML client stack on one TPU chip, in one process.

Phases, in order; any failure exits non-zero and nothing is carried on from:

1. Device check: the first JAX device must be a TPU.  There is no CPU
   fallback.
2. Train over BuffetFS: mamba2-130m at its published widths, 8 x 2048
   tokens per step, on a 4-server in-process BuffetCluster with a corpus
   generated from a fixed seed, the DataPipeline prefetch path and async
   checkpoints.  Run A trains 6 steps with checkpoints at steps 3 and 6.
   Step 6's MANIFEST is then unlinked, as if the job died before that save
   committed.  Run B, a new Trainer on the same BuffetFS directory, must
   resume at step 3 and finish step 6 on A's loss (1e-3 relative).
3. Serve: stablelm-3b at full width and depth, 3 requests of batch 4, each
   a 128-token prompt generating 16 tokens.  Tokens must lie in
   [0, vocab), and the two identical requests must return identical tokens.

Timings and memory printed on the way are bring-up output, not benchmark
metrics.  The last line of stdout is one JSON object naming the device.

    python3 chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

RESUME_RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_device():
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: platform={dev.platform} "
        f"device_kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"[smoke] FAIL: needs a TPU, found {dev.platform}")
    return dev


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def ckpt_payload_bytes(ckpt, step: int) -> int:
    return sum(int(np.prod(leaf["shape"])) * np.dtype(leaf["dtype"]).itemsize
               for leaf in ckpt.manifest(step).leaves)


def train_phase(tc) -> None:
    """Run A uninterrupted, uncommit its last checkpoint, resume as run B
    from the one before, and hold B's final loss to A's."""
    from repro.launch.train import Trainer

    kill_step, resume_step = tc.steps, tc.steps - tc.ckpt_every

    a = Trainer(tc)
    t0 = time.perf_counter()
    out_a = a.run()
    wall_a = time.perf_counter() - t0
    saved = a.ckpt.steps()
    written = sum(ckpt_payload_bytes(a.ckpt, s) for s in saved)
    log(f"run A: {tc.steps} steps in {wall_a:.3f}s (compile included); "
        f"checkpoints at steps {saved}, {written} payload bytes written")
    for step, loss in sorted(out_a["losses"].items()):
        log(f"run A step {step} loss {loss!r}")
    a.lib.unlink(f"{a.ckpt._step_dir(kill_step)}/MANIFEST")
    a.shutdown()
    del a
    gc.collect()

    b = Trainer(tc)
    t0 = time.perf_counter()
    b.init_or_restore()
    restore_s = time.perf_counter() - t0
    if b.start_step != resume_step:
        raise SystemExit(f"[smoke] FAIL: run B resumed at step {b.start_step},"
                         f" expected {resume_step}")
    t0 = time.perf_counter()
    out_b = b.run()
    wall_b = time.perf_counter() - t0
    written_b = ckpt_payload_bytes(b.ckpt, tc.steps)
    log(f"run B: init+restore {restore_s:.3f}s, resumed at step "
        f"{b.start_step}, steps {b.start_step + 1}-{tc.steps} in "
        f"{wall_b:.3f}s; {written_b} payload bytes written")
    for step, loss in sorted(out_b["losses"].items()):
        log(f"run B step {step} loss {loss!r}")
    b.shutdown()
    del b
    gc.collect()

    losses = list(out_a["losses"].values()) + list(out_b["losses"].values())
    if not all(math.isfinite(v) for v in losses):
        raise SystemExit("[smoke] FAIL: non-finite loss")
    la, lb = out_a["final_loss"], out_b["final_loss"]
    rel = abs(lb - la) / abs(la)
    log(f"step {tc.steps} loss: A {la!r}, B {lb!r}, relative difference "
        f"{rel!r} (limit {RESUME_RTOL})")
    if not rel <= RESUME_RTOL:
        raise SystemExit("[smoke] FAIL: resumed run diverged from run A")


def serve_phase(arch: str, *, reduced: bool, batch: int, prompt_len: int,
                new_tokens: int, max_len: int) -> None:
    from repro.launch.serve import Server

    t0 = time.perf_counter()
    srv = Server(arch, reduced=reduced, max_len=max_len)
    log(f"server {arch}: init {time.perf_counter() - t0:.3f}s")
    vocab = srv.cfg.vocab_size
    rng = np.random.default_rng(0)
    p0, p1 = (rng.integers(1, vocab, size=(batch, prompt_len)).astype(np.int32)
              for _ in range(2))
    outs = []
    for i, prompts in enumerate((p0, p1, p0)):
        out = srv.generate(prompts, new_tokens)
        toks = out["tokens"]
        if toks.shape != (batch, new_tokens):
            raise SystemExit(f"[smoke] FAIL: tokens shape {toks.shape}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise SystemExit(f"[smoke] FAIL: token outside [0, {vocab})")
        warm = "cold (compile included)" if i == 0 else "warm"
        log(f"request {i + 1} ({warm}): prefill {out['prefill_s']:.4f}s, "
            f"decode {out['decode_tok_per_s']:.1f} tok/s, "
            f"first tokens {toks[0, :8].tolist()}")
        outs.append(toks)
    if not np.array_equal(outs[0], outs[2]):
        raise SystemExit("[smoke] FAIL: identical requests 1 and 3 differ")
    log("requests 1 and 3 (identical prompts) returned identical tokens")


def main() -> None:
    dev = check_device()

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.train import TrainerConfig

    cache_dir = use_compile_cache()
    log(f"compile cache: {cache_dir}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        tc = TrainerConfig(arch="mamba2-130m", reduced=False, steps=6,
                           global_batch=8, seq_len=2048, ckpt_every=3,
                           log_every=1, run_name="smoke", data_dir=work)
        train_phase(tc)
    log(f"peak device bytes after training: {peak_bytes(dev)}")

    serve_phase("stablelm-3b", reduced=False, batch=4, prompt_len=128,
                new_tokens=16, max_len=512)
    log(f"peak device bytes after serving: {peak_bytes(dev)}")
    entries = list(Path(cache_dir).glob("*"))
    log(f"compile cache entries in {cache_dir}: {len(entries)}")

    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
