"""Mamba2 (SSD — state-space duality) layer in pure JAX.

Chunked SSD algorithm (arXiv:2405.21060): within-chunk computation is a
masked quadratic form (MXU-friendly), across chunks a tiny sequential scan
carries the [H, P, N] state.  Decode is the O(1) recurrence
    h_t = a_t * h_{t-1} + (dt_t x_t) outer B_t ;  y_t = C_t . h_t + D x_t
which is what makes SSM/hybrid architectures runnable at 500k context.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, SSMConfig
from .layers import _dense_init, apply_norm

Params = Dict[str, Any]


def ssm_dims(cfg: ModelConfig) -> Dict[str, int]:
    s: SSMConfig = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return dict(d_inner=d_inner, n_heads=n_heads, head_dim=s.head_dim,
                d_state=s.d_state, n_groups=s.n_groups, d_conv=s.d_conv,
                conv_dim=d_inner + 2 * s.n_groups * s.d_state)


def init_ssm(cfg: ModelConfig, key) -> Tuple[Params, Any]:
    dm = ssm_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    in_dim = 2 * dm["d_inner"] + 2 * dm["n_groups"] * dm["d_state"] + dm["n_heads"]
    p = {
        "in_proj": _dense_init(ks[0], (d, in_dim), d),
        "conv_w": _dense_init(ks[1], (dm["d_conv"], dm["conv_dim"]), dm["d_conv"]),
        "conv_b": jnp.zeros((dm["conv_dim"],), jnp.bfloat16),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, dm["n_heads"], dtype=jnp.float32)),
        "D": jnp.ones((dm["n_heads"],), jnp.float32),
        "dt_bias": jnp.zeros((dm["n_heads"],), jnp.float32),
        "out_norm": jnp.ones((dm["d_inner"],), jnp.bfloat16),
        "out_proj": _dense_init(ks[2], (dm["d_inner"], d), dm["d_inner"]),
    }
    a = {
        "in_proj": ("embed", "mlp"), "conv_w": (None, "mlp"), "conv_b": ("mlp",),
        "A_log": ("heads_nosplit",), "D": ("heads_nosplit",),
        "dt_bias": ("heads_nosplit",), "out_norm": ("mlp",),
        "out_proj": ("mlp", "embed"),
    }
    return p, a


def _split_proj(cfg: ModelConfig, proj: jnp.ndarray):
    dm = ssm_dims(cfg)
    di, gn, h = dm["d_inner"], dm["n_groups"] * dm["d_state"], dm["n_heads"]
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [di + 2 * gn], axis=-1)
    return z, xbc, dt  # gate, conv input, dt logits


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv over S.  xbc [B,S,C]; w [W,C].  Returns (y, new_state)
    where state is the trailing W-1 inputs for decode continuation."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)                  # [B, S+W-1, C]
    y = sum(xp[:, i : i + xbc.shape[1]] * w[i] for i in range(width)) + b
    new_state = xp[:, xp.shape[1] - (width - 1):]
    return jax.nn.silu(y.astype(jnp.float32)).astype(xbc.dtype), new_state


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, chunk: int):
    """Chunked SSD scan from a zero state.

    x  [B,S,H,P]  inputs per head
    dt [B,S,H]    softplus'd timestep
    a_log [H]     A = -exp(a_log)
    B,C [B,S,N]   (single group, broadcast over heads)
    Returns y [B,S,H,P], h_final [B,H,P,N].
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    A = -jnp.exp(a_log.astype(jnp.float32))                    # [H]
    loga = dtc * A                                             # [B,NC,L,H]
    cum = jnp.cumsum(loga, axis=2)                             # within-chunk cumsum

    xdt = xc.astype(jnp.float32) * dtc[..., None]              # dt-scaled input

    # ---- intra-chunk (quadratic, causal-masked) ----
    # att[i,j] = exp(cum_i - cum_j) * (C_i . B_j),  j <= i
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # [B,NC,L,L,H]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = jnp.where(causal[None, None, :, :, None], seg, -jnp.inf)
    cb = jnp.einsum("bcin,bcjn->bcij", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))                    # [B,NC,L,L]
    att = jnp.exp(seg) * cb[..., None]                         # [B,NC,L,L,H]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att, xdt)

    # ---- chunk summary states ----
    # S_c = sum_j exp(cum_last - cum_j) B_j (dt_j x_j)^T  -> [B,NC,H,P,N]
    dec_to_end = jnp.exp(cum[:, :, -1:, :] - cum)              # [B,NC,L,H]
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", dec_to_end, Bc.astype(jnp.float32), xdt)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                    # [B,NC,H]

    # ---- inter-chunk recurrence (tiny sequential scan) ----
    def step(hprev, inp):
        st, dec = inp                                          # [B,H,P,N], [B,H]
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev                                     # emit state ENTERING chunk

    h_last, h_enter = jax.lax.scan(
        step, jnp.zeros((b, h, p, n), jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)                 # [B,NC,H,P,N]

    # ---- inter-chunk contribution ----
    dec_from_start = jnp.exp(cum)                              # [B,NC,L,H]
    y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp",
                         Cc.astype(jnp.float32), dec_from_start, h_enter)

    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y, h_last


def ssm_fwd(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
            state: Optional[Dict[str, jnp.ndarray]] = None):
    """Full Mamba2 block.  If `state` given (decode), runs the recurrence on
    a short chunk and returns the updated state."""
    dm = ssm_dims(cfg)
    proj = jnp.einsum("bsd,di->bsi", x, p["in_proj"])
    z, xbc, dt_raw = _split_proj(cfg, proj)
    conv_state = state["conv"] if state is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    di, gn = dm["d_inner"], dm["n_groups"] * dm["d_state"]
    xs, B, C = jnp.split(xbc, [di, di + gn], axis=-1)
    bsz, s = x.shape[0], x.shape[1]
    xh = xs.reshape(bsz, s, dm["n_heads"], dm["head_dim"])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if state is None:
        chunk = min(cfg.ssm.chunk, s)
        y, h_last = ssd_chunked(xh, dt, p["A_log"], B, C, chunk)
    else:
        # decode: sequential recurrence over the (short) s dimension
        A = -jnp.exp(p["A_log"].astype(jnp.float32))

        def step(h, inp):
            xt, dtt, Bt, Ct = inp
            a = jnp.exp(dtt * A)                               # [B,H]
            hn = (h * a[..., None, None]
                  + jnp.einsum("bhp,bn->bhpn", xt.astype(jnp.float32) * dtt[..., None],
                               Bt.astype(jnp.float32)))
            yt = jnp.einsum("bhpn,bn->bhp", hn, Ct.astype(jnp.float32))
            return hn, yt

        h0 = state["ssm"].astype(jnp.float32)
        h_last, ys = jax.lax.scan(
            step, h0,
            (xh.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
             B.transpose(1, 0, 2), C.transpose(1, 0, 2)))
        y = ys.transpose(1, 0, 2, 3)

    y = y + xh.astype(jnp.float32) * p["D"][..., None]
    y = y.reshape(bsz, s, di)
    # gated RMSNorm then output projection
    y = apply_norm({"scale": p["out_norm"]},
                   (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype))
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    new_state = {"conv": new_conv.astype(jnp.bfloat16),
                 "ssm": h_last.astype(jnp.float32)}
    return out, new_state


def init_ssm_state(cfg: ModelConfig, batch: int, n_ssm_layers: int
                   ) -> Dict[str, jnp.ndarray]:
    dm = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((n_ssm_layers, batch, dm["d_conv"] - 1, dm["conv_dim"]),
                          jnp.bfloat16),
        "ssm": jnp.zeros((n_ssm_layers, batch, dm["n_heads"], dm["head_dim"],
                          dm["d_state"]), jnp.float32),
    }
