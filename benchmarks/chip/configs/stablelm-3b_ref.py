"""Plain float32 reference of the stablelm-3b decoder (stabilityai/
stablelm-3b-4e1t): pre-LayerNorm blocks, multi-head attention with partial
rotary embeddings (the first `partial_rotary_factor` of each head, halves
rotated), SwiGLU MLP, untied LM head.

Nothing here imports the program.  The weights are made from the run's key
layer by layer, by the recipe the system under test uses for its random
init (split the key over the layers, normal draws scaled by 1/sqrt(fan-in),
rounded to the stored bfloat16; a rare element may round one ulp apart
where XLA fuses the scaling differently), and used in float32 at `highest`
matmul precision.  One layer's weights live at a time, so the whole model never
sits on the device in float32.

`precision="fp8"` is the control: every projection and the LM head take
their operands rounded to float8 e4m3 (per-tensor scale), one step below
the bfloat16 that the configuration states.
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


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "hkv": c["num_key_value_heads"], "dh": d // h,
            "ff": c["intermediate_size"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"]}


def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(fan_in))
            ).astype(jnp.bfloat16).astype(F32)


def layer_weights(c: Dict[str, Any], key, i):
    m = dims(c)
    d, h, hkv, dh, ff = m["d"], m["h"], m["hkv"], m["dh"], m["ff"]
    k = jax.random.split(key, m["layers"] + 3)[i]
    ks = jax.random.split(k, 4)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    return {
        "wq": _normal(ka[0], (d, h, dh), d), "wk": _normal(ka[1], (d, hkv, dh), d),
        "wv": _normal(ka[2], (d, hkv, dh), d), "wo": _normal(ka[3], (h, dh, d), h * dh),
        "gate": _normal(km[0], (d, ff), d), "up": _normal(km[1], (d, ff), d),
        "down": _normal(km[2], (ff, d), ff),
    }


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


def _layernorm(x, eps):
    # the random init's LayerNorm has unit scale and zero bias
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, c):
    dh = x.shape[-1]
    rot = int(dh * c["partial_rotary_factor"])
    rot -= rot % 2
    t = x.shape[1]
    freqs = 1.0 / (c["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs            # t, rot/2
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


def block(h, w, c, precision):
    """One decoder layer over full sequences h [n, t, d], causal."""
    eps = c["layer_norm_eps"]
    x = _layernorm(h, eps)
    q = _rope(_mm("btd,dhk->bthk", x, w["wq"], precision), c)
    k = _rope(_mm("btd,dhk->bthk", x, w["wk"], precision), c)
    v = _mm("btd,dhk->bthk", x, w["wv"], precision)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=HI) / math.sqrt(q.shape[-1])
    t = h.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v, precision=HI)
    h = h + _mm("bqhk,hkd->bqd", a, w["wo"], precision)
    x = _layernorm(h, eps)
    g = jax.nn.silu(_mm("btd,df->btf", x, w["gate"], precision))
    u = _mm("btd,df->btf", x, w["up"], precision)
    return h + _mm("btf,fd->btd", g * u, w["down"], precision)


@partial(jax.jit, static_argnames=("c_items", "precisions"))
def _layer(hs, key, i, c_items, precisions):
    c = dict(c_items)
    w = layer_weights(c, key, i)
    return tuple(block(h, w, c, p) for h, p in zip(hs, precisions))


@partial(jax.jit, static_argnames=("c_items", "precisions"))
def _head(hs, key, rows, cols, c_items, precisions):
    c = dict(c_items)
    _, head = embed_weights(c, key)
    out = []
    for h, p in zip(hs, precisions):
        x = _layernorm(h[rows, cols], c["layer_norm_eps"])
        out.append(_mm("md,dv->mv", x, head, p))
    return tuple(out)


def logits_at(c: Dict[str, Any], key, seqs: Sequence[np.ndarray],
              positions: Sequence[Sequence[int]],
              precisions: Sequence[str] = ("f32",)) -> List[np.ndarray]:
    """Logits [M, V] at `positions[j]` of each token sequence `seqs[j]`, for
    each precision.  Sequences are right-padded into one batch (causal
    attention keeps the padding out of every position that is read)."""
    c_items = tuple(sorted((k, v) for k, v in c.items()
                           if isinstance(v, (int, float, str, bool))))
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for j, s in enumerate(seqs):
        toks[j, : len(s)] = s
    rows = np.concatenate([np.full(len(p), j) for j, p in enumerate(positions)])
    cols = np.concatenate([np.asarray(p) for p in positions])
    with jax.default_matmul_precision("highest"):
        tok, _ = jax.jit(partial(embed_weights, c))(key)
        h0 = tok[jnp.asarray(toks)]
        del tok
        hs = tuple(h0 for _ in precisions)
        for i in range(c["num_hidden_layers"]):
            hs = _layer(hs, key, jnp.int32(i), c_items, tuple(precisions))
        out = _head(hs, key, jnp.asarray(rows), jnp.asarray(cols), c_items,
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
