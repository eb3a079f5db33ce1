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
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig
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

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(dim: int, theta: float, yarn: Optional[YarnConfig] = None
               ) -> jnp.ndarray:
    """Rotary frequencies of `dim` rotated dims.  With YaRN (DeepSeek-V2's
    form), frequency i is the original one for i up to the dimension that
    turns `beta_fast` times over the original window, the original over
    `factor` from the one that turns `beta_slow` times, and a linear blend
    of the two between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn is None:
        return freqs

    def dim_at(turns):
        return (dim * math.log(yarn.original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_at(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_at(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               fraction: float = 1.0, yarn: Optional[YarnConfig] = None
               ) -> jnp.ndarray:
    """x: [..., S, H, dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta, yarn)                 # [rot/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, rot/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if yarn is not None:
        mag = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * mag, sin * mag
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


class KeyGroups(NamedTuple):
    """How `blocked_causal_attention` splits its queries: chunks of `chunk`
    queries, run in consecutive `groups` of (first chunk, chunks, key
    extent), which score `share` of the chunks' whole score square."""
    chunk: int
    groups: Tuple[Tuple[int, int, int], ...]
    share: float


MAX_KEY_GROUPS = 8   # each group adds one attention body to the program


def causal_key_groups(sq: int, q_chunk: int) -> KeyGroups:
    """Split `sq` causal queries into the fewest chunks of at most `q_chunk`,
    all of one length, and the chunks into at most `MAX_KEY_GROUPS`
    consecutive groups whose sizes differ by at most one.  A group's keys
    end at its last real query, so the key blocks wholly in its chunks'
    future are never scored: G equal groups score (G+1)/(2G) of the square,
    one chunk all of it."""
    nchunks = -(-sq // q_chunk)
    chunk = -(-sq // nchunks)
    ngroups = min(nchunks, MAX_KEY_GROUPS)
    groups, first = [], 0
    for g in range(ngroups):
        n = nchunks // ngroups + (g < nchunks % ngroups)
        groups.append((first, n, min(sq, (first + n) * chunk)))
        first += n
    share = sum(n * extent for _, n, extent in groups) / (nchunks * sq)
    return KeyGroups(chunk, tuple(groups), share)


def blocked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             scale: float, *, cache=None, cache_len=None,
                             q_chunk: int = 512) -> jnp.ndarray:
    """Memory-bounded causal GQA attention.

    q [B,Sq,H,dh]; k,v [B,Sq,Hkv,dh], the keys and values of the same
    positions as q.  Streams over query chunks in a loop so peak memory is
    O(q_chunk * T) per head instead of O(Sq * T): mandatory at 4k-32k
    sequence lengths on 16GB HBM.  Over several chunks, the chunks run in
    groups (`causal_key_groups`), one loop after another, and each chunk
    scores only the keys up to its group's last query: the entries left
    out are those the causal mask would zero.

    With `cache` = (ck, cv) [B,T,Hkv,dh], q/k/v are a new chunk that follows
    the cache's first `cache_len` entries: every query also attends to those,
    read in place.  One softmax spans the cache block and the chunk block.

    Any Sq works: the queries split into the fewest chunks of at most
    `q_chunk`, all of one length, the last padded with queries whose rows
    are dropped from the result.
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    q_chunk, groups, _ = causal_key_groups(sq, q_chunk)
    nchunks = sum(n for _, n, _ in groups)
    pad = nchunks * q_chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(b, nchunks, q_chunk, hkv, rep, dh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s_idx = jnp.arange(sq)
    if cache is not None:
        ckf, cvf = (c.astype(jnp.float32) for c in cache)
        t = ckf.shape[1]
        cache_mask = jnp.arange(t) < cache_len                  # [T]

    def one_chunk(qc, ci, extent):                              # qc [B,qc,G,R,dh]
        kc, vc, k_idx = ((kf, vf, s_idx) if extent == sq else
                         (kf[:, :extent], vf[:, :extent], s_idx[:extent]))
        sc = jnp.einsum("bsgrd,btgd->bgrst", qc, kc) * scale    # [B,G,R,qc,E]
        q_idx = ci * q_chunk + jnp.arange(q_chunk)
        mask = k_idx[None, :] <= q_idx[:, None]                 # [qc, E]
        sc = jnp.where(mask[None, None, None], sc, -1e30)
        if cache is None:
            w = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bgrst,btgd->bsgrd", w, vc)       # [B,qc,G,R,dh]
        scc = jnp.einsum("bsgrd,btgd->bgrst", qc, ckf) * scale  # [B,G,R,qc,T]
        scc = jnp.where(cache_mask, scc, -1e30)
        w = jax.nn.softmax(jnp.concatenate([scc, sc], axis=-1), axis=-1)
        return (jnp.einsum("bgrst,btgd->bsgrd", w[..., :t], cvf)
                + jnp.einsum("bgrst,btgd->bsgrd", w[..., t:], vc))

    dv = v.shape[-1]  # may differ from q/k head dim (MLA)
    if len(groups) == 1:
        out = jax.lax.map(lambda ci: one_chunk(qg[:, ci], ci, sq),
                          jnp.arange(nchunks))          # [NC,B,qc,G,R,dv]
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pad, h, dv)
    else:
        # one group after another, each chunk writing its rows into one
        # output buffer: no group's rows are held beside it
        out = jnp.zeros((b, sq + pad, h, dv), q.dtype)
        for first, n, extent in groups:
            qs = qg[:, first:first + n]                 # the group's chunks

            def write(i, o):
                rows = one_chunk(qs[:, i], first + i, extent)
                return jax.lax.dynamic_update_slice_in_dim(
                    o, rows.reshape(b, q_chunk, h, dv).astype(q.dtype),
                    (first + i) * q_chunk, axis=1)
            out = jax.lax.fori_loop(0, n, write, out)
    # cast back to the storage dtype at the boundary: keeps the fwd output
    # AND its backward cotangent chain (the TP partial-sum all-reduces) in
    # bf16 instead of f32 — halves the dominant collective (§Perf iter-3)
    return out[:, :sq].astype(q.dtype)


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
            cache_len=cache_pos, q_chunk=q_chunk)
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


def _mla_q(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions):
    m = cfg.mla
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
        cq = apply_norm({"scale": p["q_norm"]}, cq)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, yarn=cfg.yarn)
    return q_nope, q_rope


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times mscale(mscale_all_dim)^2 under YaRN."""
    m, y = cfg.mla, cfg.yarn
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


MLA_PREFILL_Q_CHUNK = 256


def prefill_score_share(cfg: ModelConfig, s: int) -> float:
    """The share of the causal score square that each attention layer of a
    prefill of `s` tokens scores (`causal_key_groups` at the prefill's query
    chunk: `mla_fwd`'s, else `attention_fwd`'s default 512); 1.0 for a model
    without attention."""
    if cfg.family == "ssm":
        return 1.0
    q_chunk = MLA_PREFILL_Q_CHUNK if cfg.mla is not None else 512
    return causal_key_groups(s, q_chunk).share


def mla_fwd(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions,
            *, kv_cache: Optional[Dict[str, jnp.ndarray]] = None,
            cache_pos=None):
    """MLA attention.  Without a cache (training) and in prefill (a Python-0
    `cache_pos`: nothing is cached yet) K and V are expanded from the latent
    and attention runs blocked over [nope | rope] heads.  In decode the
    query is absorbed into the latent space (q_nope W_uk), so attention
    reads the cache's (kv_lora + rope) entries of each token where they lie
    in the stacked cache, and scores the chunk's own new entries beside them
    under one softmax.  With a cache, the second result is the chunk's
    {ckv, krope} in the cache's dtype, for the caller to write at
    `cache_pos` (see `write_cache`), as `attention_fwd`'s is."""
    m = cfg.mla
    scale = mla_softmax_scale(cfg)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv, k_rope_raw = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    ckv = apply_norm({"scale": p["kv_norm"]}, ckv)
    k_rope = apply_rope(k_rope_raw[..., None, :], positions, cfg.rope_theta,
                        yarn=cfg.yarn)[..., 0, :]
    new_cache = None
    if kv_cache is not None:
        new_cache = {"ckv": ckv.astype(kv_cache["ckv"].dtype),
                     "krope": k_rope.astype(kv_cache["krope"].dtype)}
        ckv, k_rope = new_cache["ckv"], new_cache["krope"]

    if kv_cache is None or (isinstance(cache_pos, int) and cache_pos == 0):
        # expand K/V and run blocked attention with concatenated
        # [nope | rope] head dims (rope part broadcast across heads); the
        # expanded K and V stay in float32, as decode's absorbed products do
        h = cfg.n_heads
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"],
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"],
                       preferred_element_type=jnp.float32)
        k_rope = k_rope.astype(jnp.float32)
        q_cat = constrain_heads(jnp.concatenate([q_nope, q_rope], axis=-1))
        k_cat = constrain_heads(jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_rope.shape[:2], h, m.qk_rope_dim))],
            axis=-1))
        # prefill takes 256-query chunks: a 4,096-token prefill of 16 rows
        # then holds 1 GB of f32 scores a chunk, not 2 GB, and fits a v5e
        # beside its cache; training keeps the default 512
        with jax.named_scope("mla.attend"):
            out = constrain_heads(blocked_causal_attention(
                q_cat, k_cat, constrain_heads(v), scale,
                q_chunk=512 if kv_cache is None else MLA_PREFILL_Q_CHUNK))
    else:
        f32 = jnp.float32
        cc, cr = kv_cache["ckv"], kv_cache["krope"]         # [B,T,r], [B,T,dr]
        # absorption: q' = W_uk^T q_nope lives in the latent space
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(f32),
                           p["wk_b"].astype(f32))
        qr = q_rope.astype(f32)
        with jax.named_scope("mla.attend"):
            s_cache = (jnp.einsum("bshr,btr->bhst", q_lat, cc.astype(f32))
                       + jnp.einsum("bshk,btk->bhst", qr, cr.astype(f32))) * scale
            s_cache = jnp.where(jnp.arange(cc.shape[1]) < cache_pos, s_cache,
                                -1e30)
            s_new = (jnp.einsum("bshr,btr->bhst", q_lat, ckv.astype(f32))
                     + jnp.einsum("bshk,btk->bhst", qr, k_rope.astype(f32))) * scale
            sq = x.shape[1]
            causal = jnp.arange(sq)[None, :] <= jnp.arange(sq)[:, None]
            s_new = jnp.where(causal, s_new, -1e30)
            w = jax.nn.softmax(jnp.concatenate([s_cache, s_new], axis=-1),
                               axis=-1)
            t = cc.shape[1]
            lat = (jnp.einsum("bhst,btr->bshr", w[..., :t], cc.astype(f32))
                   + jnp.einsum("bhst,btr->bshr", w[..., t:], ckv.astype(f32)))
        out = jnp.einsum("bshr,rhk->bshk", lat, p["wv_b"].astype(f32))
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
    e, n = mo.n_experts, mo.held   # the router scores all; n are held here
    p = {
        "router": _dense_init(ks[0], (d, e), d, dtype=jnp.float32),
        "wi_gate": _dense_init(ks[1], (n, d, ff), d),
        "wi_up": _dense_init(ks[2], (n, d, ff), d),
        "wo": _dense_init(ks[3], (n, ff, d), ff),
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


MOE_TOKEN_CHUNK = 4096    # a chunk's pairs: 24,576 rows of d, 100 MB at 2048


def _held_experts(p: Params, xt: jnp.ndarray, top_idx: jnp.ndarray,
                  top_w: jnp.ndarray, mo: MoEConfig) -> jnp.ndarray:
    """The held experts' part of the layer for tokens xt [t, d] routed to
    top_idx [t, k] (router-wide ids; the held experts are the first
    `mo.held`) with weights top_w: every pair on a held expert is computed.
    Returns [t, d] float32."""
    t, d = xt.shape
    k = top_idx.shape[1]
    n = mo.held
    held = top_idx < n
    gid = jnp.where(held, top_idx, n).reshape(-1)          # n: held elsewhere
    order = jnp.argsort(gid, stable=True)                  # [t*k], held first
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    sizes = jnp.zeros((n + 1,), jnp.int32).at[gid].add(1)[:n]
    xs = xt[order // k]                                    # [t*k, d]
    g = jax.lax.ragged_dot(xs, p["wi_gate"], sizes)
    u = jax.lax.ragged_dot(xs, p["wi_up"], sizes)
    hid = (jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u)
    eo = jax.lax.ragged_dot(hid, p["wo"], sizes)           # [t*k, d]
    # rows past the held pairs belong to no group: zero them explicitly
    eo = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], eo, 0)
    w = jnp.where(held, top_w, 0.0).astype(jnp.float32)
    return (eo[inv].reshape(t, k, d).astype(jnp.float32)
            * w[..., None]).sum(axis=1)


def _dropless(p: Params, xt: jnp.ndarray, top_idx: jnp.ndarray,
              top_w: jnp.ndarray, mo: MoEConfig) -> jnp.ndarray:
    """`_held_experts` over chunks of at most MOE_TOKEN_CHUNK tokens, all of
    one length; the last is padded with tokens routed to no held expert."""
    t, d = xt.shape
    k = top_idx.shape[1]
    nc = -(-t // MOE_TOKEN_CHUNK)
    if nc == 1:
        return _held_experts(p, xt, top_idx, top_w, mo)
    c = -(-t // nc)
    pad = nc * c - t
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
        top_idx = jnp.pad(top_idx, ((0, pad), (0, 0)), constant_values=mo.held)
        top_w = jnp.pad(top_w, ((0, pad), (0, 0)))
    y = jax.lax.map(lambda a: _held_experts(p, *a, mo),
                    (xt.reshape(nc, c, d), top_idx.reshape(nc, c, k),
                     top_w.reshape(nc, c, k)))
    return y.reshape(nc * c, d)[:t]


def _with_capacity(p: Params, xt: jnp.ndarray, top_idx: jnp.ndarray,
                   top_w: jnp.ndarray, mo: MoEConfig) -> jnp.ndarray:
    """The held experts' part with a fixed capacity per expert: each expert
    takes at most ceil(t k / n_experts * capacity_factor) of its pairs, in
    token order, and drops the rest.  The [experts, capacity, d] buffers
    partition along an expert-sharded mesh axis.  Returns [t, d] float32."""
    t, d = xt.shape
    k = top_idx.shape[1]
    e, n = mo.n_experts, mo.held
    held = top_idx < n
    top_idx = jnp.where(held, top_idx, n)    # held elsewhere: sorts last

    # ---- position-in-expert via 1-D sort (O(t*k) memory, not O(t*k*e)) ----
    flat_e = top_idx.reshape(-1)
    counts = jnp.zeros((n + 1,), jnp.int32).at[flat_e].add(1)
    capacity = int(max(1, math.ceil(t * k / e * mo.capacity_factor)))
    order = jnp.argsort(flat_e, stable=True)                       # [t*k]
    ranks = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    offsets = jnp.cumsum(counts) - counts
    pos_flat = ranks - offsets[flat_e]                             # [t*k]
    keep = (pos_flat < capacity).reshape(t, k) & held
    pos = jnp.clip(pos_flat, 0, capacity - 1).reshape(t, k)
    top_idx = jnp.minimum(top_idx, n - 1)

    # ---- dispatch: k sequential scatters, each reading xt in place ----
    buf = jnp.zeros((n, capacity, d), xt.dtype)
    for j in range(k):
        src = xt * keep[:, j : j + 1].astype(xt.dtype)
        buf = buf.at[top_idx[:, j], pos[:, j]].add(src)

    # expert FFNs: [e, c, d] x [e, d, f]; silu in fp32, product kept bf16
    # (the [e, capacity, ff] intermediates dominate MoE activation memory)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"]
                               ).astype(jnp.float32)).astype(xt.dtype)
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    eo = jnp.einsum("ecf,efd->ecd", g * u, p["wo"])

    # ---- combine: k gathers, weighted accumulation ----
    y = jnp.zeros((t, d), jnp.float32)
    for j in range(k):
        w = (top_w[:, j] * keep[:, j]).astype(jnp.float32)
        y = y + eo[top_idx[:, j], pos[:, j]].astype(jnp.float32) * w[:, None]
    return y


def _experts_split() -> bool:
    """Whether the jit traces under a mesh that splits the experts: their
    logical axis's mesh axis has more than one device."""
    from ..runtime.sharding import LOGICAL_TO_MESH   # imports the models
    mesh = jax.sharding.get_abstract_mesh()
    axis = LOGICAL_TO_MESH["experts"]
    return not mesh.empty and mesh.shape.get(axis, 1) > 1


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k MoE over the routed experts held here, plus the shared experts.
    The router scores all `n_experts`; pairs on experts held elsewhere add
    nothing here (under expert parallelism their chips add them).  Shared
    experts run for every token.  Returns (y, aux_loss).

    On one device the layer is dropless: each pair on a held expert is
    computed, the pairs sorted by expert and each expert weight one grouped
    product over the held experts (`lax.ragged_dot`), in token chunks of at
    most MOE_TOKEN_CHUNK.  Under a mesh that splits the experts, GSPMD runs
    `ragged_dot` on every shard's tokens and all-reduces the result (1.7x
    the temporaries and all-reduce bytes of a train step on a 4-way expert
    split), so there each expert takes a fixed capacity of pairs instead
    and the [experts, capacity, d] buffers partition with the experts."""
    mo: MoEConfig = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.n_experts, mo.top_k
    xt = x.reshape(t, d)

    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
        if mo.router == "sigmoid":           # deepseek-v3 gating
            scores = jax.nn.sigmoid(logits)
            sel_scores = scores + p["router_bias"]  # bias for load balance only
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            sel_scores = scores
        _, top_idx = jax.lax.top_k(sel_scores, k)                 # [t, k]
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)     # [t, k]
        if mo.router == "sigmoid":
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-9)
        top_w = top_w * mo.router_scale

        # load-balancing aux loss (switch-style) from the assignments per
        # expert, without materializing [t, k, e]
        counts = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
        aux = (counts / t * scores.mean(0)).sum() * e / k

    with jax.named_scope("moe.experts"):
        routed = _with_capacity if _experts_split() else _dropless
        y = routed(p, xt, top_idx, top_w, mo)

    if mo.n_shared:
        with jax.named_scope("moe.shared"):
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
