"""Plain float32 reference of mamba2 (arXiv:2405.21060) training, for the
configuration files that name this reference.

Nothing here imports the program.  The weights are made from the run's key
by the same recipe the system under test uses for its random init (split
the key over the layers, normal draws scaled by 1/sqrt(fan-in), rounded to
the stored bfloat16), so the two start from equal weights without the
reference taking any array from the program.

Forward, loss and backward run in float32 at `highest` matmul precision.
The SSD mixer is the paper's minimal chunked algorithm ("ssd_minimal",
Listing 1).  Parameters are stored between steps in the dtype the
configuration states for each leaf (bfloat16 weights, float32 A_log, D and
dt_bias); the AdamW update itself is float32.  Departures from the
published model that the configuration file lists (norm epsilon, vocab
rows, float32 residual) are taken from the file.

`precision="fp8"` is the control: every projection and the LM head take
their operands rounded to float8 e4m3 (per-tensor scale), one step below
the bfloat16 that the configuration states for them.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d = c["d_model"]
    di = c["expand"] * d
    n = c["ngroups"] * c["d_state"]
    h = di // c["headdim"]
    return {"d": d, "di": di, "n": n, "h": h, "p": c["headdim"],
            "w": c["d_conv"], "conv": di + 2 * n, "in": 2 * di + 2 * n + h,
            "v": c["vocab_size"], "layers": c["n_layer"], "chunk": c["chunk_size"]}


def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(fan_in))
            ).astype(jnp.bfloat16)


def init_params(c: Dict[str, Any], key) -> Dict[str, Any]:
    m = dims(c)
    keys = jax.random.split(key, m["layers"] + 3)
    layers = []
    for i in range(m["layers"]):
        ks = jax.random.split(keys[i], 4)
        layers.append({
            "norm": {"scale": jnp.ones((m["d"],), jnp.bfloat16)},
            "ssm": {
                "in_proj": _normal(ks[0], (m["d"], m["in"]), m["d"]),
                "conv_w": _normal(ks[1], (m["w"], m["conv"]), m["w"]),
                "conv_b": jnp.zeros((m["conv"],), jnp.bfloat16),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, m["h"], dtype=F32)),
                "D": jnp.ones((m["h"],), F32),
                "dt_bias": jnp.zeros((m["h"],), F32),
                "out_norm": jnp.ones((m["di"],), jnp.bfloat16),
                "out_proj": _normal(ks[2], (m["di"], m["d"]), m["di"]),
            },
        })
    ek = jax.random.split(keys[-1], 2)
    return {
        "embed": {"tok": _normal(ek[0], (m["v"], m["d"]), m["d"])},
        "final_norm": {"scale": jnp.ones((m["d"],), jnp.bfloat16)},
        "blocks": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q8(x):
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, x, w, precision):
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def segsum(x):
    """x [..., T] -> [..., T, T]: sum of x[j+1..i] at (i, j), -inf above the
    diagonal (stable: no difference of two cumulative sums)."""
    t = x.shape[-1]
    x = jnp.broadcast_to(x[..., None], (*x.shape, t))
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), s, -jnp.inf)


def ssd_minimal(x, a, b, c, block):
    """The paper's Listing 1.  x [B,T,H,P] (dt-scaled), a [B,T,H] (dt*A),
    b, c [B,T,H,N] -> y [B,T,H,P]."""
    bs, t, h, p = x.shape
    nc = t // block
    x, a, b, c = (z.reshape(bs, nc, block, *z.shape[2:]) for z in (x, a, b, c))
    a = jnp.transpose(a, (0, 3, 1, 2))                       # b h c l
    a_cum = jnp.cumsum(a, axis=-1)
    L = jnp.exp(segsum(a))                                   # b h c l l
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", c, b, L, x,
                        precision=HI)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)          # b h c l
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", b, decay_states, x,
                        precision=HI)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states, precision=HI)
    states = new_states[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cum),
                       precision=HI)
    return (y_diag + y_off).reshape(bs, t, h, p)


def mamba2_mixer(p, x, c, precision):
    m = dims(c)
    proj = _mm("bsd,de->bse", x, p["in_proj"], precision)
    z, xbc, dt = jnp.split(proj, [m["di"], m["di"] + m["conv"]], axis=-1)
    w = m["w"]
    xp = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + xbc.shape[1]] * p["conv_w"][k] for k in range(w))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, B, C = jnp.split(xbc, [m["di"], m["di"] + m["n"]], axis=-1)
    bs, t = x.shape[:2]
    xh = xs.reshape(bs, t, m["h"], m["p"])
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # b t h
    A = -jnp.exp(p["A_log"])
    Bh = jnp.broadcast_to(B[:, :, None, :], (bs, t, m["h"], m["n"]))
    Ch = jnp.broadcast_to(C[:, :, None, :], (bs, t, m["h"], m["n"]))
    y = ssd_minimal(xh * dt[..., None], dt * A, Bh, Ch, min(m["chunk"], t))
    y = (y + xh * p["D"][:, None]).reshape(bs, t, m["di"])
    y = _rmsnorm(y * jax.nn.silu(z), p["out_norm"], c["norm_epsilon"])
    return _mm("bsi,id->bsd", y, p["out_proj"], precision)


def nll_sum(params, tokens, labels, c, precision="f32", ce_chunk=512):
    """Summed next-token NLL of a block of rows, all in float32."""
    p = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
    h = p["embed"]["tok"][tokens]

    @jax.checkpoint
    def layer(hh, lp):
        x = _rmsnorm(hh, lp["norm"]["scale"], c["norm_epsilon"])
        return hh + mamba2_mixer(lp["ssm"], x, c, precision), None

    h, _ = jax.lax.scan(layer, h, p["blocks"])
    h = _rmsnorm(h, p["final_norm"]["scale"], c["norm_epsilon"])
    bs, t, d = h.shape
    cs = min(ce_chunk, t)
    while t % cs:
        cs -= 1

    @jax.checkpoint
    def ce(total, xs):
        hc, lc = xs
        logits = _mm("bsd,vd->bsv", hc, p["embed"]["tok"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - pick), None

    hs = h.reshape(bs, t // cs, cs, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(bs, t // cs, cs).transpose(1, 0, 2)
    total, _ = jax.lax.scan(ce, jnp.zeros((), F32), (hs, ls))
    return total


# ---------------------------------------------------------------------------
# AdamW, as the configuration's traffic file states it
# ---------------------------------------------------------------------------

def lr_at(o: Dict[str, Any], step: int) -> float:
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(o["warmup_steps"], 1)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    return o["lr"] * (o["min_lr_frac"] + (1 - o["min_lr_frac"])
                      * 0.5 * (1 + math.cos(math.pi * prog)))


@partial(jax.jit, static_argnames=("o_items",))
def _adamw(params, grads, m, v, step, lr, o_items):
    o = dict(o_items)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1c = 1.0 - o["b1"] ** step
    b2c = 1.0 - o["b2"] ** step

    def upd(p, g, mm, vv):
        g = g * scale
        mm = o["b1"] * mm + (1 - o["b1"]) * g
        vv = o["b2"] * vv + (1 - o["b2"]) * g * g
        delta = (mm / b1c) / (jnp.sqrt(vv / b2c) + o["eps"])
        if p.ndim > 1:
            delta = delta + o["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * delta).astype(p.dtype), mm, vv, g

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def _norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): float(np.sqrt(np.sum(
        np.square(np.asarray(x, np.float64))))) for kp, x in flat}


def train_readings(c: Dict[str, Any], tr: Dict[str, Any], key,
                   batches: List[Dict[str, np.ndarray]], precision: str = "f32",
                   drop_half: bool = False) -> Dict[str, Any]:
    """Losses of the given steps, the first step's gradient as AdamW gets
    it (clipped), and the parameters' change over all the steps, by leaf.

    `drop_half` is a planted fault: each step sees only the first half of
    its rows and takes the mean over them."""
    o = dict(tr["optimizer"])
    with jax.default_matmul_precision("highest"):
        params = jax.jit(partial(init_params, c))(key)
        p0 = params
        m = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
        v = m
        rows = tr.get("ref_rows_per_block") or 1
        grad_fn = jax.jit(jax.value_and_grad(
            lambda pf, tk, lb: nll_sum(pf, tk, lb, c, precision)))
        losses, first = [], None
        for i, b in enumerate(batches, start=1):
            tok, lab = b["tokens"], b["labels"]
            if drop_half:
                tok, lab = tok[: len(tok) // 2], lab[: len(lab) // 2]
            pf = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
            total, grads = 0.0, None
            for r in range(0, len(tok), rows):
                val, g = grad_fn(pf, jnp.asarray(tok[r:r + rows]),
                                 jnp.asarray(lab[r:r + rows]))
                total = total + val
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            count = float(tok.size)
            grads = jax.tree_util.tree_map(lambda g: g / count, grads)
            losses.append(float(total) / count)
            params, m, v, used = _adamw(params, grads, m, v, jnp.float32(i),
                                        jnp.float32(lr_at(o, i)),
                                        tuple(sorted(o.items())))
            if first is None:
                first = _norms(used)
        change = _norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(F32) - b.astype(F32), params, p0))
    return {"losses": losses, "grad_norms": first, "change_norms": change}
