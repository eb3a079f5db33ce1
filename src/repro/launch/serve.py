"""Batched serving driver: prefill + greedy decode of one static batch.

Loads (or initializes) a model, serves a batch of token prompts with a KV /
SSM-state cache, and returns greedy tokens.  The same `serve_step` the
multi-pod dry-run lowers runs here on the default device, so what is served
is exactly what was dry-run.

Reduced config by default; `--full` for the published widths;
`--trace-dir DIR` records the program's spans and a profiler trace:
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --tokens 32
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs import get_config
from ..models import init_cache, init_model
from ..models.layers import prefill_score_share
from ..runtime.steps import prefill_step, serve_step
from .compile_cache import use_compile_cache
from .trace_dir import traced


class Server:
    def __init__(self, arch: str, *, reduced: bool = True,
                 max_len: int = 512, params=None) -> None:
        cfg = get_config(arch)
        self.cfg = cfg.reduced() if reduced else cfg
        self.max_len = max_len
        if params is None:
            params, _ = init_model(self.cfg, jax.random.PRNGKey(0))
        self.params = params
        self._prefill = jax.jit(
            lambda p, c, b: prefill_step(p, c, b, self.cfg),
            donate_argnums=(1,))
        self._decode = jax.jit(
            lambda p, c, b, pos: serve_step(p, c, b, pos, self.cfg),
            donate_argnums=(1,))
        self.batches_served = 0

    def _embed_stub(self, tokens) -> Optional[np.ndarray]:
        """Stub modality frontend: deterministic pseudo-embeddings per token
        (audio/vlm archs take precomputed frame/patch embeddings).  Only
        these archs bring the tokens to the host."""
        if self.cfg.frontend is None:
            return None
        rng = np.random.default_rng(1234)
        table = rng.standard_normal((self.cfg.vocab_size, self.cfg.d_model),
                                    dtype=np.float32) * 0.02
        return table[np.asarray(tokens)]

    def generate(self, prompts: np.ndarray, n_tokens: int
                 ) -> Dict[str, np.ndarray]:
        """prompts [B, S0] int32 -> generated [B, n_tokens]."""
        b, s0 = prompts.shape
        k = self.batches_served
        self.batches_served += 1
        with obs.span("serve.generate", batch=k):
            with obs.span("serve.init_cache"):
                cache = init_cache(self.cfg, b, self.max_len)
                batch = {"tokens": jnp.asarray(prompts)}
                emb = self._embed_stub(prompts)
                if emb is not None:
                    batch["embeds"] = jnp.asarray(emb, jnp.bfloat16)
            share = prefill_score_share(self.cfg, s0)
            with obs.span("serve.prefill", attn_score_share=share):
                t0 = time.perf_counter()
                logits, cache = self._prefill(self.params, cache, batch)
                logits.block_until_ready()
                prefill_s = time.perf_counter() - t0

            outs: List[np.ndarray] = []
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            t0 = time.perf_counter()
            # Token i goes to the host only after the step that consumes it
            # is dispatched, so the device runs that step while the host
            # waits: the host's per-step time stays off the device's path.
            for i in range(n_tokens):
                with obs.span("serve.decode_step", token=i):
                    with obs.span("serve.dispatch"):
                        step_batch = {"tokens": tok[:, None]}
                        emb = self._embed_stub(step_batch["tokens"])
                        if emb is not None:
                            step_batch["embeds"] = jnp.asarray(emb, jnp.bfloat16)
                        logits, cache = self._decode(self.params, cache,
                                                     step_batch,
                                                     jnp.int32(s0 + i))
                        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    with obs.span("serve.token_sync"):
                        outs.append(np.asarray(tok))
                    tok = nxt
            with obs.span("serve.token_sync"):
                tok.block_until_ready()
            decode_s = time.perf_counter() - t0
        return {"tokens": np.stack(outs, 1),
                "prefill_s": prefill_s,
                "decode_tok_per_s": b * n_tokens / max(decode_s, 1e-9)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth instead of reduced")
    ap.add_argument("--trace-dir", default=None,
                    help="record program spans and a profiler trace of the "
                         "job into this directory (spans.jsonl at exit)")
    args = ap.parse_args()
    use_compile_cache()
    with traced(args.trace_dir):
        srv = Server(args.arch, reduced=not args.full)
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, srv.cfg.vocab_size,
                               size=(args.batch, args.prompt_len)
                               ).astype(np.int32)
        out = srv.generate(prompts, args.tokens)
    print(f"[serve] arch={args.arch} prefill={out['prefill_s']:.2f}s "
          f"decode={out['decode_tok_per_s']:.1f} tok/s")
    print(out["tokens"][:, :8])


if __name__ == "__main__":
    main()
