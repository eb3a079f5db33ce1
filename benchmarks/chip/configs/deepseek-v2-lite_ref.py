"""Plain float32 reference of one chip's share of DeepSeek-V2-Lite
(deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434): pre-RMSNorm blocks,
multi-head latent attention in its expanded form (K and V made from the
normalised latent for every position, no absorption, no cache) with YaRN
rotary embeddings on the 64 rope dims, a dense SwiGLU first layer, then
DeepSeekMoE layers: a softmax router over all `router_experts`, greedy top-k
without renormalisation, the part of the held experts (the first
n_routed_experts of the router's outputs) computed densely for every
token and weighted by its routing weight (zero where the expert was not
picked), plus the shared experts; untied LM head.

Nothing here imports the program.  The weights are made from the run's key
layer by layer, by the recipe the system under test uses for its random
init (split the key over the layers, normal draws scaled by 1/sqrt(fan-in),
rounded to the stored bfloat16, the router kept in float32; a rare element
may round one ulp apart where XLA fuses the scaling differently), and used
in float32 at `highest` matmul precision.  One layer's weights live at a
time.  Attention runs over blocks of queries so that a 4k sequence's scores
never sit on the device whole.  Routing follows the reference's own float32
scores, so a near-tie that the program's bfloat16 path breaks the other way
shows in the compared logits.

`precision="fp8"` is the control: every projection, the router, the expert
products and the LM head take their operands rounded to float8 e4m3
(per-tensor scale), one step below the bfloat16 that the configuration
states.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "ff": c["intermediate_size"], "eff": c["moe_intermediate_size"],
            "e": c["router_experts"], "held": c["n_routed_experts"],
            "k": c["num_experts_per_tok"],
            "shared": c["n_shared_experts"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"]}


def _normal(key, shape, fan_in, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(fan_in))
            ).astype(dtype).astype(F32)


def _mlp_weights(key, d, ff):
    ks = jax.random.split(key, 3)
    return {"gate": _normal(ks[0], (d, ff), d), "up": _normal(ks[1], (d, ff), d),
            "down": _normal(ks[2], (ff, d), ff)}


def layer_weights(c: Dict[str, Any], key, i):
    """Layer i's weights; the MoE weights when `i` is past the dense layers
    (i may be traced: both kinds are made, the caller keeps one)."""
    m = dims(c)
    d, h, r = m["d"], m["h"], m["r"]
    k = jax.random.split(key, m["layers"] + 3)[i]
    ks = jax.random.split(k, 4)
    ka = jax.random.split(ks[0], 6)
    w = {"attn": {
        "wq": _normal(ka[0], (d, h, m["nope"] + m["rope"]), d),
        "wkv_a": _normal(ka[2], (d, r + m["rope"]), d),
        "wk_b": _normal(ka[3], (r, h, m["nope"]), r),
        "wv_b": _normal(ka[4], (r, h, m["dv"]), r),
        "wo": _normal(ka[5], (h, m["dv"], d), h * m["dv"]),
    }}
    w["mlp"] = _mlp_weights(ks[1], d, m["ff"])
    km = jax.random.split(ks[1], 5)
    n, eff = m["held"], m["eff"]
    w["moe"] = {
        "router": _normal(km[0], (d, m["e"]), d, dtype=F32),
        "gate": _normal(km[1], (n, d, eff), d), "up": _normal(km[2], (n, d, eff), d),
        "down": _normal(km[3], (n, eff, d), eff),
        "shared": _mlp_weights(km[4], d, eff * m["shared"]),
    }
    return w


def embed_weights(c: Dict[str, Any], key):
    m = dims(c)
    ek = jax.random.split(jax.random.split(key, m["layers"] + 3)[-1], 2)
    return (_normal(ek[0], (m["v"], m["d"]), m["d"]),
            _normal(ek[1], (m["d"], m["v"]), m["d"]))


def _q8(x):
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, x, w, precision):
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, eps):
    # the random init's RMSNorm scales are ones
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's DeepseekV2YarnRotaryEmbedding and softmax scale)
# ---------------------------------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: Dict[str, Any]) -> np.ndarray:
    """The rope dims' inverse frequencies, as the source's YaRN computes them."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    orig = rs["original_max_position_embeddings"]

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / rs["factor"]
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def yarn_cos_sin_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    return yarn_get_mscale(rs["factor"], rs["mscale"]) \
        / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])


def softmax_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, c):
    """x [n, t, ..., rope]: the two halves rotated by position."""
    t = x.shape[1]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(c), F32)
    mag = yarn_cos_sin_scale(c)
    shape = (1, t) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * mag).reshape(shape)
    sin = (jnp.sin(ang) * mag).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def attention(h, w, c, precision):
    """Expanded MLA over full sequences h [n, t, d], causal."""
    m = dims(c)
    eps = c["rms_norm_eps"]
    x = _rmsnorm(h, eps)
    q = _mm("btd,dhk->bthk", x, w["wq"], precision)
    q_nope, q_rope = q[..., : m["nope"]], _rope(q[..., m["nope"]:], c)
    kv = _mm("btd,dr->btr", x, w["wkv_a"], precision)
    ckv = _rmsnorm(kv[..., : m["r"]], eps)
    k_rope = _rope(kv[..., m["r"]:], c)                         # [n, t, rope]
    k_nope = _mm("btr,rhk->bthk", ckv, w["wk_b"], precision)
    v = _mm("btr,rhk->bthk", ckv, w["wv_b"], precision)
    scale = softmax_scale(c)
    t = h.shape[1]
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, t)
        s = (jnp.einsum("bqhk,bthk->bhqt", q_nope[:, q0:q1], k_nope[:, :q1],
                        precision=HI)
             + jnp.einsum("bqhk,btk->bhqt", q_rope[:, q0:q1], k_rope[:, :q1],
                          precision=HI)) * scale
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1),
                               v[:, :q1], precision=HI))
    return h + _mm("bqhk,hkd->bqd", jnp.concatenate(outs, axis=1), w["wo"],
                   precision)


def _swiglu(x, w, precision):
    g = jax.nn.silu(_mm("btd,df->btf", x, w["gate"], precision))
    return _mm("btf,fd->btd", g * _mm("btd,df->btf", x, w["up"], precision),
               w["down"], precision)


def routing(x, w, c, precision="f32"):
    """Each token's weight for each held expert [n, t, held]: its softmax
    score where the expert is among the token's greedy top-k over all
    router experts, else 0; and the scores [n, t, e]."""
    m = dims(c)
    scores = jax.nn.softmax(_mm("btd,de->bte", x, w["router"], precision), -1)
    _, top = jax.lax.top_k(scores, m["k"])
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None],
        jnp.arange(scores.shape[1])[None, :, None], top].set(True)
    weight = jnp.where(picked, scores, 0.0) * c["routed_scaling_factor"]
    return weight[..., : m["held"]], scores


def moe(h, w, c, precision):
    """The held experts' part of a DeepSeekMoE layer and its shared experts,
    over full sequences h [n, t, d]."""
    x = _rmsnorm(h, c["rms_norm_eps"])
    weight, _ = routing(x, w, c, precision)
    y = _swiglu(x, w["shared"], precision)
    for j in range(weight.shape[-1]):
        ew = {"gate": w["gate"][j], "up": w["up"][j], "down": w["down"][j]}
        y = y + _swiglu(x, ew, precision) * weight[..., j:j + 1]
    return h + y


def dense(h, w, c, precision):
    return h + _swiglu(_rmsnorm(h, c["rms_norm_eps"]), w, precision)


@partial(jax.jit, static_argnames=("c_items", "precisions", "is_moe"))
def _layer(hs, key, i, c_items, precisions, is_moe):
    c = _unfreeze(c_items)
    w = layer_weights(c, key, i)
    out = []
    for h, p in zip(hs, precisions):
        h = attention(h, w["attn"], c, p)
        out.append(moe(h, w["moe"], c, p) if is_moe else dense(h, w["mlp"], c, p))
    return tuple(out)


@partial(jax.jit, static_argnames=("c_items", "precisions"))
def _head(hs, key, rows, cols, c_items, precisions):
    c = _unfreeze(c_items)
    _, head = embed_weights(c, key)
    return tuple(_mm("md,dv->mv", _rmsnorm(h[rows, cols], c["rms_norm_eps"]),
                     head, p) for h, p in zip(hs, precisions))


def _freeze(c: Dict[str, Any]):
    """The configuration as a hashable static argument (numbers and
    strings, and the rope_scaling group)."""
    items = [(k, v) for k, v in c.items() if isinstance(v, (int, float, str, bool))]
    items.append(("rope_scaling", tuple(sorted(c["rope_scaling"].items()))))
    return tuple(sorted(items))


def _unfreeze(items) -> Dict[str, Any]:
    c = dict(items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    return c


def hidden_states(c: Dict[str, Any], key, toks, precisions=("f32",)):
    """The final layer's outputs [n, t, d] of token batch toks, per precision."""
    c_items = _freeze(c)
    m = dims(c)
    with jax.default_matmul_precision("highest"):
        tok, _ = jax.jit(partial(embed_weights, c))(key)
        h0 = tok[jnp.asarray(toks)]
        del tok
        hs = tuple(h0 for _ in precisions)
        for i in range(m["layers"]):
            hs = _layer(hs, key, jnp.int32(i), c_items, tuple(precisions),
                        i >= m["dense"])
    return hs


def logits_at(c: Dict[str, Any], key, seqs: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]],
              precisions: Sequence[str] = ("f32",)) -> List[np.ndarray]:
    """Logits [M, V] at `positions[j]` of each token sequence `seqs[j]`, for
    each precision.  Sequences are right-padded into one batch (causal
    attention keeps the padding out of every position that is read)."""
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for j, s in enumerate(seqs):
        toks[j, : len(s)] = s
    rows = np.concatenate([np.full(len(p), j) for j, p in enumerate(positions)])
    cols = np.concatenate([np.asarray(p) for p in positions])
    hs = hidden_states(c, key, toks, tuple(precisions))
    with jax.default_matmul_precision("highest"):
        out = _head(hs, key, jnp.asarray(rows), jnp.asarray(cols), _freeze(c),
                    tuple(precisions))
    return [np.asarray(o) for o in out]


def served_gaps(c: Dict[str, Any], key, prompts: Sequence[np.ndarray],
                served: Sequence[np.ndarray], control: bool = False
                ) -> Dict[str, float]:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of every sequence.  With
    `control`, also the widest gap of the token that the fp8 control puts
    first at the same positions."""
    seqs, pos = [], []
    for p, s in zip(prompts, served):
        seqs.append(np.concatenate([p, s[:-1]]).astype(np.int32))
        pos.append(np.arange(len(p) - 1, len(p) - 1 + len(s)))
    precisions = ("f32", "fp8") if control else ("f32",)
    outs = logits_at(c, key, seqs, pos, precisions)
    ref = outs[0]
    want = np.concatenate([np.asarray(s) for s in served])
    best = ref.max(axis=1)
    gaps = {"served_logit_gap": float(np.max(best - ref[np.arange(len(want)), want]))}
    if control:
        pick = outs[1].argmax(axis=1)
        gaps["control_logit_gap"] = float(np.max(best - ref[np.arange(len(pick)), pick]))
    gaps["tokens"] = int(len(want))
    return gaps
