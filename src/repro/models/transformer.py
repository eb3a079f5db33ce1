"""Model assembly: every assigned architecture builds from this module.

Families:
  dense / moe / audio / vlm  -> transformer decoder (GQA or MLA attention,
                                dense-MLP or MoE FFN, optional modality stub)
  ssm                        -> pure Mamba2 stack
  hybrid                     -> Jamba-style repeating block
                                (1 attention : 7 mamba, MoE every 2nd layer)

Compile-time discipline (one CPU core compiles 60-72-layer full configs):
* all identical layers are STACKED and driven by `lax.scan`;
* MoE models with a dense prefix unroll only the prefix;
* hybrid models scan over period-blocks (the 8-layer block body unrolls).

Public entry points (used by runtime/launch):
  init_model(cfg, key)                 -> (params, axes)
  loss_fn(params, batch, cfg)          -> (loss, metrics)       [train]
  prefill(params, batch, cfg, cache)   -> (logits_last, cache)  [serve]
  decode_step(params, batch, cfg, cache, pos) -> (logits, cache)
  init_cache(cfg, batch, max_len)      -> cache pytree
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..context import constrain_bsd
from . import layers as L
from . import ssm as S

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# per-layer init/apply for the transformer families
# ---------------------------------------------------------------------------

def _layer_is_moe(cfg: ModelConfig, layer_idx: int) -> bool:
    mo = cfg.moe
    if mo is None:
        return False
    if layer_idx < mo.n_dense_prefix:
        return False
    return (layer_idx - mo.n_dense_prefix) % mo.layer_period == 0


def _init_tf_layer(cfg: ModelConfig, key, *, moe: bool):
    ks = jax.random.split(key, 4)
    attn_p, attn_a = (L.init_mla(cfg, ks[0]) if cfg.mla is not None
                      else L.init_attention(cfg, ks[0]))
    n1p, n1a = L.init_norm(cfg)
    n2p, n2a = L.init_norm(cfg)
    if moe:
        ffn_p, ffn_a = L.init_moe(cfg, ks[1])
    else:
        ffn_p, ffn_a = L.init_mlp(cfg, ks[1])
    p = {"attn_norm": n1p, "attn": attn_p, "ffn_norm": n2p, "ffn": ffn_p}
    a = {"attn_norm": n1a, "attn": attn_a, "ffn_norm": n2a, "ffn": ffn_a}
    return p, a


def _apply_tf_layer(cfg: ModelConfig, p: Params, h: jnp.ndarray, positions,
                    *, moe: bool, cache=None, cache_pos=None):
    attn_in = L.apply_norm(p["attn_norm"], h)
    if cfg.mla is not None:
        y, new_cache = L.mla_fwd(p["attn"], attn_in, cfg, positions,
                                 kv_cache=cache, cache_pos=cache_pos)
    else:
        y, new_cache = L.attention_fwd(p["attn"], attn_in, cfg, positions,
                                       kv_cache=cache, cache_pos=cache_pos)
    # §Perf iter-1: constrain the TP contraction output to the sharded
    # activation layout BEFORE the residual add, so GSPMD lowers the partial
    # sums as reduce-scatter (1x bytes) instead of all-reduce (2x) + reslice
    h = h + constrain_bsd(y)
    ffn_in = L.apply_norm(p["ffn_norm"], h)
    if moe:
        y, aux = L.apply_moe(p["ffn"], ffn_in, cfg)
    else:
        y, aux = L.apply_mlp(p["ffn"], ffn_in, cfg), jnp.float32(0.0)
    return h + constrain_bsd(y), new_cache, aux


# ---------------------------------------------------------------------------
# ssm layer (pure mamba stack)
# ---------------------------------------------------------------------------

def _init_ssm_layer(cfg: ModelConfig, key):
    np_, na = L.init_norm(cfg)
    sp, sa = S.init_ssm(cfg, key)
    return {"norm": np_, "ssm": sp}, {"norm": na, "ssm": sa}


def _apply_ssm_layer(cfg: ModelConfig, p: Params, h: jnp.ndarray, *, state=None):
    y, new_state = S.ssm_fwd(p["ssm"], L.apply_norm(p["norm"], h), cfg,
                             state=state)
    return h + constrain_bsd(y), new_state


# ---------------------------------------------------------------------------
# hybrid (Jamba) period-block
# ---------------------------------------------------------------------------

def _init_hybrid_block(cfg: ModelConfig, key):
    hy = cfg.hybrid
    ks = jax.random.split(key, hy.period * 2 + 1)
    sub_p, sub_a = [], []
    for i in range(hy.period):
        kk = ks[2 * i : 2 * i + 2]
        if i == hy.attn_index:
            mp, ma = L.init_attention(cfg, kk[0])
            mixer = "attn"
        else:
            mp, ma = S.init_ssm(cfg, kk[0])
            mixer = "ssm"
        n1p, n1a = L.init_norm(cfg)
        n2p, n2a = L.init_norm(cfg)
        moe = (i % hy.moe_every) == 1
        fp, fa = (L.init_moe(cfg, kk[1]) if moe else L.init_mlp(cfg, kk[1]))
        sub_p.append({"mixer_norm": n1p, "mixer": mp, "ffn_norm": n2p, "ffn": fp})
        sub_a.append({"mixer_norm": n1a, "mixer": ma, "ffn_norm": n2a, "ffn": fa})
    return {"layers": sub_p}, {"layers": sub_a}


def _apply_hybrid_block(cfg: ModelConfig, p: Params, h: jnp.ndarray, positions,
                        *, cache=None, cache_pos=None):
    """cache (decode): {"kv": {k,v}, "conv": ..., "ssm": ...} for this block.
    The new cache holds the block's new SSM states and only the chunk's new
    K/V under "kv", for the caller to write (`layers.write_cache`)."""
    hy = cfg.hybrid
    aux_total = jnp.float32(0.0)
    new_cache: Dict[str, Any] = {}
    ssm_i = 0
    for i, lp in enumerate(p["layers"]):
        x = L.apply_norm(lp["mixer_norm"], h)
        if i == hy.attn_index:
            kv = cache["kv"] if cache is not None else None
            y, nkv = L.attention_fwd(lp["mixer"], x, cfg, positions,
                                     kv_cache=kv, cache_pos=cache_pos)
            if nkv is not None:
                new_cache["kv"] = nkv
        else:
            st = (None if cache is None else
                  {"conv": cache["conv"][ssm_i], "ssm": cache["ssm"][ssm_i]})
            y, nst = S.ssm_fwd(lp["mixer"], x, cfg, state=st)
            new_cache.setdefault("conv", []).append(nst["conv"])
            new_cache.setdefault("ssm", []).append(nst["ssm"])
            ssm_i += 1
        h = h + constrain_bsd(y)
        x = L.apply_norm(lp["ffn_norm"], h)
        if (i % hy.moe_every) == 1:
            y, aux = L.apply_moe(lp["ffn"], x, cfg)
            aux_total = aux_total + aux
        else:
            y = L.apply_mlp(lp["ffn"], x, cfg)
        h = h + constrain_bsd(y)
    if "conv" in new_cache:
        new_cache["conv"] = jnp.stack(new_cache["conv"])
        new_cache["ssm"] = jnp.stack(new_cache["ssm"])
    return h, new_cache, aux_total


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_model(cfg: ModelConfig, key) -> Tuple[Params, Any]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    ep, ea = L.init_embed(cfg, keys[-1])
    fnp, fna = L.init_norm(cfg)
    params: Params = {"embed": ep, "final_norm": fnp}
    axes: Dict[str, Any] = {"embed": ea, "final_norm": fna}

    if cfg.family == "ssm":
        lp = [_init_ssm_layer(cfg, keys[i]) for i in range(cfg.n_layers)]
        params["blocks"] = _stack([p for p, _ in lp])
        axes["blocks"] = lp[0][1]
    elif cfg.family == "hybrid":
        nb = cfg.n_layers // cfg.hybrid.period
        bp = [_init_hybrid_block(cfg, keys[i]) for i in range(nb)]
        params["blocks"] = _stack([p for p, _ in bp])
        axes["blocks"] = bp[0][1]
    else:
        prefix_n = cfg.moe.n_dense_prefix if cfg.moe else 0
        prefix = [_init_tf_layer(cfg, keys[i], moe=False) for i in range(prefix_n)]
        rest = [_init_tf_layer(cfg, keys[prefix_n + i], moe=_layer_is_moe(cfg, prefix_n + i))
                for i in range(cfg.n_layers - prefix_n)]
        if prefix:
            params["prefix"] = [p for p, _ in prefix]
            axes["prefix"] = [a for _, a in prefix]
        params["blocks"] = _stack([p for p, _ in rest])
        axes["blocks"] = rest[0][1]
        if cfg.mtp:  # deepseek-v3 multi-token-prediction head
            mp, ma = _init_tf_layer(cfg, keys[-2], moe=False)
            np_, na_ = L.init_norm(cfg)
            params["mtp"] = {"layer": mp, "norm": np_}
            axes["mtp"] = {"layer": ma, "norm": na_}
    return params, axes


# ---------------------------------------------------------------------------
# forward (train / prefill: full-sequence, no cache)
# ---------------------------------------------------------------------------

def _embed_inputs(params: Params, batch: Dict[str, jnp.ndarray],
                  cfg: ModelConfig) -> jnp.ndarray:
    if cfg.frontend is not None and "embeds" in batch:
        h = batch["embeds"].astype(jnp.bfloat16)  # stub modality frontend
    else:
        h = L.embed_tokens(params["embed"], batch["tokens"])
    if cfg.pos_embed == "sinusoidal":
        s = h.shape[1]
        pos0 = batch.get("pos0", 0)
        h = h + L.sinusoidal_embed(pos0 + jnp.arange(s), cfg.d_model)
    return h


def forward(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward; returns (hidden[B,S,D], aux_loss)."""
    h = constrain_bsd(_embed_inputs(params, batch, cfg))
    s = h.shape[1]
    positions = jnp.arange(s)
    aux = jnp.float32(0.0)

    # activation checkpointing: backward recomputes each layer from its input
    # (saves only the [B,S,D] carry per layer instead of every intermediate —
    # mandatory for 4k-32k training on 16GB HBM)
    remat = (jax.checkpoint if cfg.remat == "layer" else (lambda f: f))

    if cfg.family == "ssm":
        @remat
        def body(carry, lp):
            hh, ax = carry
            hh, _ = _apply_ssm_layer(cfg, lp, hh)
            return (constrain_bsd(hh), ax), None
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])
    elif cfg.family == "hybrid":
        @remat
        def body(carry, bp):
            hh, ax = carry
            hh, _, a = _apply_hybrid_block(cfg, bp, hh, positions)
            return (constrain_bsd(hh), ax + a), None
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])
    else:
        moe_rest = cfg.moe is not None

        @remat
        def prefix_body(hh, lp):
            hh, _, _ = _apply_tf_layer(cfg, lp, hh, positions, moe=False)
            return constrain_bsd(hh)

        for lp in params.get("prefix", []):
            h = prefix_body(h, lp)

        @remat
        def body(carry, lp):
            hh, ax = carry
            hh, _, a = _apply_tf_layer(cfg, lp, hh, positions, moe=moe_rest)
            return (constrain_bsd(hh), ax + a), None
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])

    h = constrain_bsd(L.apply_norm(params["final_norm"], h))
    return h, aux


def _chunked_ce(embed_params: Params, h: jnp.ndarray, labels: jnp.ndarray,
                mask: jnp.ndarray, cfg: ModelConfig, n_chunks: int = 8
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy without materializing full [B,S,V] fp32 logits: the
    sequence is processed in rematerialized chunks (peak memory = one chunk
    of logits; backward recomputes them).  Returns (sum_nll, sum_mask)."""
    b, s, d = h.shape
    while s % n_chunks:
        n_chunks -= 1
    cs = s // n_chunks

    @jax.checkpoint
    def chunk_nll(hc, lc, mc):
        logits = L.lm_logits(embed_params, hc, cfg)          # [B,cs,V] fp32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
        return (nll * mc).sum()

    def body(carry, xs):
        hc, lc, mc = xs
        return carry + chunk_nll(hc, lc, mc), None

    hs = h.reshape(b, n_chunks, cs, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n_chunks, cs).transpose(1, 0, 2)
    ms = mask.reshape(b, n_chunks, cs).transpose(1, 0, 2)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ls, ms))
    return total, mask.sum()


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            *, aux_weight: float = 0.01, ce_chunks: int = 8
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    h, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    nll_sum, msum = _chunked_ce(params["embed"], h, labels, mask, cfg,
                                n_chunks=ce_chunks)
    ce = nll_sum / jnp.maximum(msum, 1.0)
    loss = ce + aux_weight * aux
    metrics = {"loss": loss, "ce": ce, "aux": aux,
               "ppl": jnp.exp(jnp.minimum(ce, 20.0))}

    if cfg.mtp and cfg.family not in ("ssm", "hybrid"):
        # predict t+2 through one extra block on (h shifted by one token)
        positions = jnp.arange(h.shape[1])
        hm, _, _ = _apply_tf_layer(cfg, params["mtp"]["layer"], h, positions,
                                   moe=False)
        hm = L.apply_norm(params["mtp"]["norm"], hm)
        nll2, m2sum = _chunked_ce(params["embed"], hm[:, :-1], labels[:, 1:],
                                  mask[:, 1:], cfg, n_chunks=ce_chunks)
        mtp_ce = nll2 / jnp.maximum(m2sum, 1.0)
        loss = loss + 0.1 * mtp_ce
        metrics["mtp_ce"] = mtp_ce
        metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    if cfg.family == "ssm":
        return {"ssm_state": S.init_ssm_state(cfg, batch, cfg.n_layers)}
    if cfg.family == "hybrid":
        hy = cfg.hybrid
        nb = cfg.n_layers // hy.period
        kv = L.init_kv_cache(cfg, batch, max_len, nb)
        st = S.init_ssm_state(cfg, batch, nb * (hy.period - 1))
        # reshape ssm leaves to [NB, per-block, ...]
        st = jax.tree_util.tree_map(
            lambda x: x.reshape(nb, hy.period - 1, *x.shape[1:]), st)
        return {"kv": kv, "conv": st["conv"], "ssm": st["ssm"]}
    if cfg.mla is not None:
        c = L.init_mla_cache(cfg, batch, max_len, cfg.n_layers)
        return {"mla": c}
    return {"kv": L.init_kv_cache(cfg, batch, max_len, cfg.n_layers)}


def _model_step(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                cache: Dict[str, Any], cache_pos) -> Tuple[jnp.ndarray, Dict]:
    """Shared incremental forward for prefill (s>1) and decode (s=1)."""
    if cfg.pos_embed == "sinusoidal":
        batch = dict(batch, pos0=cache_pos)
    h = constrain_bsd(_embed_inputs(params, batch, cfg))
    s = h.shape[1]
    positions = cache_pos + jnp.arange(s)
    new_cache: Dict[str, Any] = {}

    if cfg.family == "ssm":
        def body(hh, xs):
            lp, st = xs
            x = L.apply_norm(lp["norm"], hh)
            y, nst = S.ssm_fwd(lp["ssm"], x, cfg, state=st)
            return constrain_bsd(hh + y), nst
        h, nst = jax.lax.scan(body, h, (params["blocks"], cache["ssm_state"]))
        new_cache["ssm_state"] = nst
    elif cfg.family == "hybrid":
        def body(hh, xs):
            bp, bc = xs
            hh, nc, _ = _apply_hybrid_block(cfg, bp, hh, positions,
                                            cache=bc, cache_pos=cache_pos)
            return constrain_bsd(hh), nc
        h, nc = jax.lax.scan(body, h, (params["blocks"], cache))
        new_cache = dict(nc, kv=L.write_cache(cache["kv"], nc["kv"], cache_pos))
    else:
        key = "mla" if cfg.mla is not None else "kv"
        # the stacked cache covers ALL layers; prefix layers use slots
        # 0..n_prefix-1, scanned layers the rest (see _serve_tf)
        h, nc = _serve_tf(params, h, cfg, cache[key], cache_pos, positions)
        new_cache[key] = nc

    h = L.apply_norm(params["final_norm"], h)
    logits = L.lm_logits(params["embed"], h[:, -1:], cfg)
    return logits, new_cache


def _serve_tf(params, h, cfg, cache, cache_pos, positions):
    """Transformer serve path: prefix layers unrolled, rest scanned; the
    stacked cache covers ALL layers (prefix first).  The layers only read
    the cache; each returns its new entries, and those are written into the
    cache once, after the layers, so the donated buffer is updated in place
    and never copied."""
    n_prefix = len(params.get("prefix", []))
    moe_rest = cfg.moe is not None

    def layer_cache(i):      # layer i's slice, read where it lies
        return jax.tree_util.tree_map(lambda x: x[i], cache)

    prefix_new = []
    for i, lp in enumerate(params.get("prefix", [])):
        h, nc, _ = _apply_tf_layer(cfg, lp, h, positions, moe=False,
                                   cache=layer_cache(i), cache_pos=cache_pos)
        prefix_new.append(nc)

    def body(hh, xs):
        lp, i = xs
        hh, nc, _ = _apply_tf_layer(cfg, lp, hh, positions, moe=moe_rest,
                                    cache=layer_cache(i), cache_pos=cache_pos)
        return constrain_bsd(hh), nc
    n_rest = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
    h, rest_new = jax.lax.scan(body, h, (params["blocks"],
                                         n_prefix + jnp.arange(n_rest)))

    if prefix_new:
        cache = L.write_cache(cache, _stack(prefix_new), cache_pos)
    return h, L.write_cache(cache, rest_new, cache_pos, layer0=n_prefix)


def prefill(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            cache: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    # a Python 0: the cache holds nothing yet, so attention skips reading it
    return _model_step(params, batch, cfg, cache, 0)


def decode_step(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                cache: Dict[str, Any], pos) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One token step against a cache filled up to `pos`."""
    return _model_step(params, batch, cfg, cache, pos)
