"""The model's SSD scan, norms and chunked cross-entropy against plain
statements of what they compute, on the CPU at small sizes: the SSD
recurrence one timestep at a time, the textbook RMSNorm and LayerNorm, and
the masked NLL of the whole logits."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.ssm import ssd_chunked
from repro.models.transformer import _chunked_ce

TOL = dict(rtol=1e-5, atol=1e-5)
# f32 chunk sums against the f64 recurrence, outputs up to ~200
TOL_SSD = dict(rtol=2e-4, atol=2e-4)
# a bf16 result holds 8 bits of mantissa
TOL_BF16 = dict(rtol=1e-2, atol=1e-2)


def _normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _f64(x):
    return np.asarray(x, np.float32).astype(np.float64)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
def ssd_recurrence(x, dt, a_log, B, C):
    """h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) outer B_t, y_t = C_t . h_t
    from h_0 = 0, one timestep at a time in f64.  x [b,s,h,p]; dt [b,s,h];
    a_log [h]; B, C [b,s,n].  Returns y [b,s,h,p] and the last state
    [b,h,p,n]."""
    x, dt, B, C = _f64(x), _f64(dt), _f64(B), _f64(C)
    A = -np.exp(_f64(a_log))
    b, s, h, p = x.shape
    state = np.zeros((b, h, p, B.shape[-1]))
    ys = []
    for t in range(s):
        decay = np.exp(dt[:, t] * A)                              # [b,h]
        state = (state * decay[:, :, None, None]
                 + np.einsum("bhp,bn->bhpn", x[:, t] * dt[:, t, :, None],
                             B[:, t]))
        ys.append(np.einsum("bhpn,bn->bhp", state, C[:, t]))
    return np.stack(ys, axis=1), state


def _ssd_inputs(key, b, s, h, p, n, dtype):
    ks = jax.random.split(key, 5)
    return (_normal(ks[0], (b, s, h, p), dtype),
            jax.nn.softplus(_normal(ks[1], (b, s, h))),
            _normal(ks[2], (h,)) * 0.1,
            _normal(ks[3], (b, s, n), dtype),
            _normal(ks[4], (b, s, n), dtype))


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 32, 4, 16, 16, 8),
    (2, 64, 8, 16, 32, 16),
    (1, 64, 8, 32, 64, 32),
    (2, 128, 2, 8, 16, 64),
])
def test_ssd_chunked_matches_recurrence(b, s, h, p, n, chunk):
    inputs = _ssd_inputs(jax.random.PRNGKey(0), b, s, h, p, n, jnp.float32)
    y, h_last = ssd_chunked(*inputs, chunk)
    y_ref, h_ref = ssd_recurrence(*inputs)
    np.testing.assert_allclose(np.asarray(y), y_ref, **TOL_SSD)
    np.testing.assert_allclose(np.asarray(h_last), h_ref, **TOL_SSD)


def test_ssd_chunked_bf16_inputs():
    """x, B and C in bf16, as the model passes them: the scan runs in f32
    on the same bf16 values the recurrence sees."""
    inputs = _ssd_inputs(jax.random.PRNGKey(7), 1, 32, 4, 16, 16,
                         jnp.bfloat16)
    y, _ = ssd_chunked(*inputs, 8)
    y_ref, _ = ssd_recurrence(*inputs)
    np.testing.assert_allclose(np.asarray(y), y_ref, **TOL_SSD)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm_ref(x, scale, eps=1e-6):
    x = _f64(x)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f64(scale)


def layernorm_ref(x, scale, bias, eps=1e-6):
    x = _f64(x)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * _f64(scale) + _f64(bias)


@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
@pytest.mark.parametrize("shape", [(8, 128), (4, 32, 128), (2, 16, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_apply_norm_matches_formula(kind, shape, dtype):
    """RMSNorm (mamba2's, deepseek's) and LayerNorm with a bias
    (stablelm's), in f32 and in the model's bf16."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = _normal(ks[0], shape, dtype) * 2.0 + 0.5
    p = {"scale": 1.0 + 0.1 * _normal(ks[1], shape[-1:], dtype)}
    if kind == "layernorm":
        p["bias"] = 0.1 * _normal(ks[2], shape[-1:], dtype)
        want = layernorm_ref(x, p["scale"], p["bias"])
    else:
        want = rmsnorm_ref(x, p["scale"])
    got = L.apply_norm(p, x)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               **(TOL if dtype == jnp.float32 else TOL_BF16))


# ---------------------------------------------------------------------------
# chunked cross-entropy
# ---------------------------------------------------------------------------
def ce_ref(logits, labels, mask):
    """Sum over rows of the masked NLL of the whole logits [..., V]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return (nll * mask).sum()


def _ce_case(b, s, vocab, dtype):
    cfg = replace(get_config("mamba2-130m"), vocab_size=vocab, d_model=64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    embed = {"tok": _normal(ks[0], (vocab, 64), dtype) * 0.5}
    h = _normal(ks[1], (b, s, 64), dtype)
    labels = jax.random.randint(ks[2], (b, s), 0, vocab)
    mask = (jax.random.uniform(ks[3], (b, s)) > 0.3).astype(jnp.float32)
    return cfg, embed, h, labels, mask


@pytest.mark.parametrize("b,s,vocab,n_chunks", [
    (2, 16, 256, 8),
    (4, 16, 512, 4),
    (1, 16, 1024, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_ce_matches_whole_logits(b, s, vocab, n_chunks, dtype):
    cfg, embed, h, labels, mask = _ce_case(b, s, vocab, dtype)
    nll, msum = _chunked_ce(embed, h, labels, mask, cfg, n_chunks=n_chunks)
    want = ce_ref(L.lm_logits(embed, h, cfg), labels, mask)
    np.testing.assert_allclose(float(nll), float(want), **TOL)
    assert float(msum) == float(mask.sum())


def test_chunked_ce_all_masked_is_zero():
    cfg, embed, h, labels, mask = _ce_case(2, 16, 256, jnp.float32)
    nll, msum = _chunked_ce(embed, h, labels, jnp.zeros_like(mask), cfg)
    assert float(nll) == 0.0 and float(msum) == 0.0
