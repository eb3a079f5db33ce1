"""Core neural layers (pure JAX, functional, scan-friendly).

Conventions:
* every `init_*` returns `(params, axes)` — `axes` mirrors `params` with a
  tuple of LOGICAL axis names per array dim; `repro.runtime.sharding` maps
  logical axes -> mesh axes (FSDP x TP x EP) in one place.
* activations are bf16, params bf16, all reductions/softmax in fp32.
* attention layouts: x [B, S, D]; q [B, S, H, dh]; kv [B, S, Hkv, dh].
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig, MoEConfig
from ..context import constrain, constrain_heads, constrain_kv

Params = Dict[str, Any]
PyTree = Any

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, in_dim, dtype=jnp.bfloat16):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _zeros(shape, dtype=jnp.bfloat16):
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return ({"scale": jnp.ones((d,), jnp.bfloat16),
                 "bias": jnp.zeros((d,), jnp.bfloat16)},
                {"scale": ("embed",), "bias": ("embed",)})
    return ({"scale": jnp.ones((d,), jnp.bfloat16)}, {"scale": ("embed",)})


def apply_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    ms = (xf * xf).mean(-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / positional embeddings
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               fraction: float = 1.0) -> jnp.ndarray:
    """x: [..., S, H, dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta)                       # [rot/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, rot/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


def sinusoidal_embed(positions: jnp.ndarray, d: int) -> jnp.ndarray:
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> Tuple[Params, PyTree]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, h, dh), d),
        "wk": _dense_init(ks[1], (d, hkv, dh), d),
        "wv": _dense_init(ks[2], (d, hkv, dh), d),
        "wo": _dense_init(ks[3], (h, dh, d), h * dh),
    }
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"], p["bk"], p["bv"] = (_zeros((h, dh)), _zeros((hkv, dh)),
                                     _zeros((hkv, dh)))
        a["bq"], a["bk"], a["bv"] = (("heads", "head_dim"),
                                     ("kv_heads", "head_dim"),
                                     ("kv_heads", "head_dim"))
    return p, a


def blocked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             scale: float, *, cache=None, cache_len=None,
                             q_chunk: int = 512) -> jnp.ndarray:
    """Memory-bounded causal GQA attention.

    q [B,Sq,H,dh]; k,v [B,Sq,Hkv,dh], the keys and values of the same
    positions as q.  Streams over query chunks with `lax.map` so peak memory
    is O(q_chunk * T) per head instead of O(Sq * T): mandatory at 4k-32k
    sequence lengths on 16GB HBM.

    With `cache` = (ck, cv) [B,T,Hkv,dh], q/k/v are a new chunk that follows
    the cache's first `cache_len` entries: every query also attends to those,
    read in place.  One softmax spans the cache block and the chunk block.
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    q_chunk = min(q_chunk, sq)
    assert sq % q_chunk == 0
    nchunks = sq // q_chunk
    qg = q.reshape(b, nchunks, q_chunk, hkv, rep, dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s_idx = jnp.arange(sq)
    if cache is not None:
        ckf, cvf = (c.astype(jnp.float32) for c in cache)
        t = ckf.shape[1]
        cache_mask = jnp.arange(t) < cache_len                  # [T]

    def one_chunk(ci):
        qc = qg[:, ci]                                          # [B,qc,G,R,dh]
        sc = jnp.einsum("bsgrd,btgd->bgrst", qc, kf) * scale    # [B,G,R,qc,Sq]
        q_idx = ci * q_chunk + jnp.arange(q_chunk)
        mask = s_idx[None, :] <= q_idx[:, None]                 # [qc, Sq]
        sc = jnp.where(mask[None, None, None], sc, -1e30)
        if cache is None:
            w = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bgrst,btgd->bsgrd", w, vf)       # [B,qc,G,R,dh]
        scc = jnp.einsum("bsgrd,btgd->bgrst", qc, ckf) * scale  # [B,G,R,qc,T]
        scc = jnp.where(cache_mask, scc, -1e30)
        w = jax.nn.softmax(jnp.concatenate([scc, sc], axis=-1), axis=-1)
        return (jnp.einsum("bgrst,btgd->bsgrd", w[..., :t], cvf)
                + jnp.einsum("bgrst,btgd->bsgrd", w[..., t:], vf))

    out = jax.lax.map(one_chunk, jnp.arange(nchunks))           # [NC,B,qc,G,R,dv]
    dv = v.shape[-1]  # may differ from q/k head dim (MLA)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, dv)
    # cast back to the storage dtype at the boundary: keeps the fwd output
    # AND its backward cotangent chain (the TP partial-sum all-reduces) in
    # bf16 instead of f32 — halves the dominant collective (§Perf iter-3)
    return out.astype(q.dtype)


def attention_fwd(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  positions: jnp.ndarray, *,
                  kv_cache: Optional[Dict[str, jnp.ndarray]] = None,
                  cache_pos=None, q_chunk: int = 512):
    """Causal self-attention.  If `kv_cache` is given, x is the new token
    chunk (decode/incremental-prefill) that follows the cache's first
    `cache_pos` entries.  The cache is only read; the second result is this
    chunk's {k, v} [B,S,Hkv,dh] in the cache's dtype, for the caller to
    write at `cache_pos` (see `write_cache`).  A `cache_pos` of Python 0
    (prefill) reads nothing from the cache."""
    dh = cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope != "none":
        frac = cfg.rope_fraction if cfg.rope == "partial" else 1.0
        q = apply_rope(q, positions, cfg.rope_theta, frac)
        k = apply_rope(k, positions, cfg.rope_theta, frac)

    scale = 1.0 / math.sqrt(dh)
    if kv_cache is None:
        # §Perf iter-2: reshard seq->heads for the attention interior (one
        # all-to-all each way) instead of per-chunk seq gathers + reduces
        q = constrain_heads(q)
        k = constrain_kv(k)
        v = constrain_kv(v)
        out = constrain_heads(blocked_causal_attention(q, k, v, scale,
                                                       q_chunk=q_chunk))
        new_kv = None
    else:
        new_kv = {"k": k.astype(kv_cache["k"].dtype),
                  "v": v.astype(kv_cache["v"].dtype)}
        empty = isinstance(cache_pos, int) and cache_pos == 0
        out = blocked_causal_attention(
            q, new_kv["k"], new_kv["v"], scale,
            cache=None if empty else (kv_cache["k"], kv_cache["v"]),
            cache_len=cache_pos, q_chunk=min(q_chunk, x.shape[1]))
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_kv


def write_cache(cache, new, cache_pos, layer0: int = 0):
    """Write each leaf of `new` [n, B, S, ...] into the stacked `cache`
    [L, B, T, ...] at layers layer0.. and positions cache_pos..: one
    in-place update of the donated buffer per leaf."""
    def put(c, n):
        start = (layer0, 0, cache_pos) + (0,) * (c.ndim - 3)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), start)
    return jax.tree_util.tree_map(put, cache, new)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_attn_layers: int) -> Dict[str, jnp.ndarray]:
    shape = (n_attn_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key) -> Tuple[Params, PyTree]:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    p: Params = {}
    a: Dict[str, Any] = {}
    if m.q_lora_rank:
        p["wq_a"] = _dense_init(ks[0], (d, m.q_lora_rank), d)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), jnp.bfloat16)
        p["wq_b"] = _dense_init(ks[1], (m.q_lora_rank, h, qk), m.q_lora_rank)
        a["wq_a"] = ("embed", "lora")
        a["q_norm"] = ("lora",)
        a["wq_b"] = ("lora", "heads", "head_dim")
    else:
        p["wq"] = _dense_init(ks[0], (d, h, qk), d)
        a["wq"] = ("embed", "heads", "head_dim")
    p["wkv_a"] = _dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), d)
    p["kv_norm"] = jnp.ones((m.kv_lora_rank,), jnp.bfloat16)
    p["wk_b"] = _dense_init(ks[3], (m.kv_lora_rank, h, m.qk_nope_dim),
                            m.kv_lora_rank)
    p["wv_b"] = _dense_init(ks[4], (m.kv_lora_rank, h, m.v_head_dim),
                            m.kv_lora_rank)
    p["wo"] = _dense_init(ks[5], (h, m.v_head_dim, d), h * m.v_head_dim)
    a.update({
        "wkv_a": ("embed", "lora"), "kv_norm": ("lora",),
        "wk_b": ("lora", "heads", "head_dim"),
        "wv_b": ("lora", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    })
    return p, a


def _mla_q(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions) :
    m = cfg.mla
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
        cq = apply_norm({"scale": p["q_norm"]}, cq)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_fwd(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions,
            *, kv_cache: Optional[Dict[str, jnp.ndarray]] = None,
            cache_pos: Optional[jnp.ndarray] = None):
    """MLA attention.  Prefill path expands K/V; decode path runs ABSORBED
    attention directly in the compressed latent space so the cache stays at
    (kv_lora + rope) per token — the whole point of MLA.  With a cache, the
    second result is the chunk's {ckv, krope} for the caller to write at
    `cache_pos`, as `attention_fwd`'s is."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv, k_rope_raw = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    ckv = apply_norm({"scale": p["kv_norm"]}, ckv)
    k_rope = apply_rope(k_rope_raw[..., None, :], positions, cfg.rope_theta)[..., 0, :]

    if kv_cache is None:
        # expand K/V and run blocked attention with concatenated
        # [nope | rope] head dims (rope part broadcast across heads)
        h = cfg.n_heads
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
        q_cat = constrain_heads(jnp.concatenate([q_nope, q_rope], axis=-1))
        k_cat = constrain_heads(jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_rope.shape[:2], h, m.qk_rope_dim))],
            axis=-1))
        out = constrain_heads(
            blocked_causal_attention(q_cat, k_cat, constrain_heads(v), scale))
        new_cache = None
    else:
        cc, cr = kv_cache["ckv"], kv_cache["krope"]
        cc = jax.lax.dynamic_update_slice(cc, ckv.astype(cc.dtype),
                                          (0, cache_pos, 0))
        cr = jax.lax.dynamic_update_slice(cr, k_rope.astype(cr.dtype),
                                          (0, cache_pos, 0))
        # absorption: q' = W_uk^T q_nope lives in the latent space
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(jnp.float32),
                           p["wk_b"].astype(jnp.float32))
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, cc.astype(jnp.float32))
                  + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                               cr.astype(jnp.float32))) * scale
        t_idx = jnp.arange(cc.shape[1])
        q_idx = cache_pos + jnp.arange(x.shape[1])
        mask = t_idx[None, :] <= q_idx[:, None]
        w = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
        lat = jnp.einsum("bhst,btr->bshr", w, cc.astype(jnp.float32))
        out = jnp.einsum("bshr,rhk->bshk", lat, p["wv_b"].astype(jnp.float32))
        new_cache = {"ckv": ckv.astype(cc.dtype), "krope": k_rope.astype(cr.dtype)}
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   n_layers: int) -> Dict[str, jnp.ndarray]:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((n_layers, batch, max_len, m.kv_lora_rank), jnp.bfloat16),
        "krope": jnp.zeros((n_layers, batch, max_len, m.qk_rope_dim), jnp.bfloat16),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":
        p = {"wi": _dense_init(ks[0], (d, ff), d),
             "wo": _dense_init(ks[1], (ff, d), ff)}
        a = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    else:  # swiglu
        p = {"wi_gate": _dense_init(ks[0], (d, ff), d),
             "wi_up": _dense_init(ks[1], (d, ff), d),
             "wo": _dense_init(ks[2], (ff, d), ff)}
        a = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"),
             "wo": ("mlp", "embed")}
    return p, a


def apply_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    # §Perf iter-3: keep the [B,S,ff] intermediates TOKEN-sharded ("bsf"
    # spec = batch x sequence-parallel): GSPMD then all-gathers the (small)
    # ff-sharded weights per layer instead of all-reducing the (huge)
    # full-sequence activations — the ZeRO-style FFN formulation
    if "wi" in p:
        h = jax.nn.gelu(constrain(jnp.einsum("bsd,df->bsf", x, p["wi"]),
                                  "bsf").astype(jnp.float32))
        return jnp.einsum("bsf,fd->bsd", h.astype(x.dtype), p["wo"])
    g = jax.nn.silu(constrain(jnp.einsum("bsd,df->bsf", x, p["wi_gate"]),
                              "bsf").astype(jnp.float32))
    u = constrain(jnp.einsum("bsd,df->bsf", x, p["wi_up"]),
                  "bsf").astype(jnp.float32)
    return jnp.einsum("bsf,fd->bsd", (g * u).astype(x.dtype), p["wo"])


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key):
    mo: MoEConfig = cfg.moe
    d = cfg.d_model
    ff = mo.d_expert_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    e = mo.n_experts
    p = {
        "router": _dense_init(ks[0], (d, e), d, dtype=jnp.float32),
        "wi_gate": _dense_init(ks[1], (e, d, ff), d),
        "wi_up": _dense_init(ks[2], (e, d, ff), d),
        "wo": _dense_init(ks[3], (e, ff, d), ff),
    }
    a = {
        "router": ("embed", "experts_nosplit"),
        "wi_gate": ("experts", "embed", "mlp"),
        "wi_up": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
    }
    if mo.router == "sigmoid":
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
        a["router_bias"] = ("experts_nosplit",)
    if mo.n_shared:
        sp, sa = init_mlp(cfg, ks[4], d_ff=ff * mo.n_shared)
        p["shared"], a["shared"] = sp, sa
    return p, a


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-based top-k MoE.  Returns (y, aux_loss)."""
    mo: MoEConfig = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.n_experts, mo.top_k
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    if mo.router == "sigmoid":           # deepseek-v3 gating
        scores = jax.nn.sigmoid(logits)
        sel_scores = scores + p["router_bias"]     # bias for load balance only
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel_scores = scores
    _, top_idx = jax.lax.top_k(sel_scores, k)                     # [t, k]
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)         # [t, k]
    if mo.router == "sigmoid":
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-9)
    top_w = top_w * mo.router_scale

    # load-balancing aux loss (switch-style) without materializing [t,k,e]:
    # fraction of assignments per expert via bincount
    flat_e = top_idx.reshape(-1)                                   # [t*k] int32
    counts = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0)
    me = counts / t
    ce = scores.mean(0)
    aux = (me * ce).sum() * e / k

    # ---- position-in-expert via 1-D sort (O(t*k) memory, not O(t*k*e)) ----
    capacity = int(max(1, math.ceil(t * k / e * mo.capacity_factor)))
    order = jnp.argsort(flat_e, stable=True)                       # [t*k]
    ranks = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    offsets = jnp.cumsum(counts.astype(jnp.int32)) - counts.astype(jnp.int32)
    pos_flat = ranks - offsets[flat_e]                             # [t*k]
    keep = (pos_flat < capacity).reshape(t, k)
    pos = jnp.clip(pos_flat, 0, capacity - 1).reshape(t, k)

    # ---- dispatch: k sequential scatters, each reading xt in place ----
    # buf/eo constrained expert-sharded ("ecd") so the scatter lowers as the
    # token->expert all-to-all and every expert FFN computes locally (§Perf)
    buf = jnp.zeros((e, capacity, d), xt.dtype)
    for j in range(k):
        src = xt * keep[:, j : j + 1].astype(xt.dtype)
        buf = buf.at[top_idx[:, j], pos[:, j]].add(src)
    buf = constrain(buf, "ecd")

    # expert FFNs: [e, c, d] x [e, d, f]; silu in fp32, product kept bf16
    # (the [e, capacity, ff] intermediates dominate MoE activation memory)
    g = jax.nn.silu(constrain(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"]),
                              "ecd").astype(jnp.float32)).astype(xt.dtype)
    u = constrain(jnp.einsum("ecd,edf->ecf", buf, p["wi_up"]), "ecd")
    eo = constrain(jnp.einsum("ecf,efd->ecd", g * u, p["wo"]), "ecd")

    # ---- combine: k gathers, weighted accumulation ----
    y = jnp.zeros((t, d), jnp.float32)
    for j in range(k):
        w = (top_w[:, j] * keep[:, j]).astype(jnp.float32)
        y = y + eo[top_idx[:, j], pos[:, j]].astype(jnp.float32) * w[:, None]

    if mo.n_shared:
        y = y + apply_mlp(p["shared"], xt[None], cfg)[0].astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# embeddings / output head
# ---------------------------------------------------------------------------

def init_embed(cfg: ModelConfig, key):
    ks = jax.random.split(key, 2)
    p = {"tok": _dense_init(ks[0], (cfg.vocab_size, cfg.d_model), cfg.d_model)}
    a = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(ks[1], (cfg.d_model, cfg.vocab_size), cfg.d_model)
        a["head"] = ("embed", "vocab")
    return p, a


def embed_tokens(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def lm_logits(p: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", h, p["tok"]).astype(jnp.float32)
    return jnp.einsum("bsd,dv->bsv", h, p["head"]).astype(jnp.float32)
