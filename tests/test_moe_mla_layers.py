"""DeepSeek-V2's layer mechanics at small sizes on the CPU: YaRN's rotary
frequencies and softmax scale against hand-computed values, the MoE layer
dropping nothing however skewed the routing, blocked attention and the MoE
token chunks at any length, and the MoE layer under an expert-split mesh."""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import YarnConfig
from repro.models import decode_step, forward, init_cache, init_model, prefill
from repro.models import layers as L
from repro.models.transformer import loss_fn

YARN = YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def test_yarn_frequencies_hand_computed():
    # 64 rope dims, theta 10000: the dimension that turns 32 times over
    # 4096 positions is 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47,
    # floored to 10; once, 22.51, ceiled to 23.  Frequencies 0-10 keep
    # theta^(-2i/64), 23-31 are divided by 40, and between they blend over
    # the ramp (i - 10) / 13.
    got = np.asarray(L.rope_freqs(64, 10000.0, YARN), np.float64)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    assert got[16] == pytest.approx(base[16] * (1 - 6 / 13) + base[16] / 40 * 6 / 13,
                                    rel=1e-6)
    np.testing.assert_allclose(np.asarray(L.rope_freqs(64, 10000.0)), base,
                               rtol=1e-6)


def test_yarn_mscale_hand_computed():
    # mscale = 0.1 * 0.707 * ln 40 + 1 = 1.260804; the softmax scale of a
    # 192-dim query is 192^-0.5 * 1.260804^2 = 0.0721688 * 1.589626
    m = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(40.0, 0.707) == pytest.approx(1.260804, rel=1e-6)
    assert m ** 2 == pytest.approx(1.589626, rel=1e-6)
    assert 192 ** -0.5 * m * m == pytest.approx(0.1147214, rel=1e-6)
    cfg = get_config("deepseek-v2-lite-16b")
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                     rel=1e-12)
    assert L.mla_softmax_scale(replace(cfg, yarn=None)) == pytest.approx(
        192 ** -0.5)
    # mscale equals mscale_all_dim, so cos and sin keep unit length
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    y = L.apply_rope(x, jnp.arange(5), 10000.0, yarn=YARN)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def _swiglu(x, g, u, o):
    return (jax.nn.silu(x @ g) * (x @ u)) @ o


@pytest.mark.parametrize("first", [2, 5])
def test_skewed_routing_drops_nothing(first):
    """Every token picks the same six experts (first .. first + 5 of 16),
    so each takes all 64 tokens, five times what a 1.25 capacity factor
    allowed; the layer, holding experts 0-7, still gives every token its
    full weighted sum over the held ones among them."""
    cfg = get_config("deepseek-v2-lite").reduced()
    cfg = replace(cfg, moe=replace(cfg.moe, n_experts=16, top_k=6, n_held=8,
                                   n_shared=0))
    d, ff = cfg.d_model, cfg.moe.d_expert_ff
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jnp.abs(jax.random.normal(ks[0], (4, 16, d))).astype(jnp.bfloat16)
    router = jnp.zeros((d, 16), jnp.float32).at[:, first:first + 6].set(0.05)
    p = {"router": router,
         "wi_gate": (jax.random.normal(ks[1], (8, d, ff)) / d ** 0.5).astype(jnp.bfloat16),
         "wi_up": (jax.random.normal(ks[2], (8, d, ff)) / d ** 0.5).astype(jnp.bfloat16),
         "wo": (jax.random.normal(ks[3], (8, ff, d)) / ff ** 0.5).astype(jnp.bfloat16)}
    y, _ = L.apply_moe(p, x, cfg)
    xf = np.asarray(x, np.float32).reshape(-1, d)
    scores = jax.nn.softmax(xf @ np.asarray(router), axis=-1)
    want = np.zeros_like(xf)
    for e in range(first, min(first + 6, 8)):   # the held ones picked
        f = [np.asarray(p[k][e], np.float32) for k in ("wi_gate", "wi_up", "wo")]
        want += np.asarray(_swiglu(xf, *f)) * np.asarray(scores[:, e:e + 1])
    got = np.asarray(y, np.float32).reshape(-1, d)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=0.02 * np.abs(want).max())


def _naive_attention(q, k, v, scale, cache=None, cache_len=0):
    """Causal GQA attention over [cache entries below cache_len | chunk]."""
    rep = q.shape[2] // k.shape[2]
    sq = q.shape[1]
    mask = np.tril(np.ones((sq, sq), bool))
    if cache is not None:
        t = cache[0].shape[1]
        k = jnp.concatenate([cache[0], k], axis=1)
        v = jnp.concatenate([cache[1], v], axis=1)
        mask = np.concatenate([np.broadcast_to(np.arange(t) < cache_len,
                                               (sq, t)), mask], axis=1)
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    w = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", w, v)


@pytest.mark.parametrize("sq,q_chunk,cached", [
    (300, 256, False),     # two chunks of 150
    (601, 512, False),     # two chunks of 301, one query of padding
    (4099, 256, False),    # a prime: 17 chunks of 242, 15 of padding
    (301, 256, True),      # after 33 of a cache's 40 entries
])
def test_blocked_attention_takes_any_length(sq, q_chunk, cached):
    ks = jax.random.split(jax.random.PRNGKey(sq), 5)
    q = jax.random.normal(ks[0], (1, sq, 4, 8))
    k = jax.random.normal(ks[1], (1, sq, 2, 8))
    v = jax.random.normal(ks[2], (1, sq, 2, 8))
    cache = ((jax.random.normal(ks[3], (1, 40, 2, 8)),
              jax.random.normal(ks[4], (1, 40, 2, 8))) if cached else None)
    got = L.blocked_causal_attention(q, k, v, 0.35, cache=cache,
                                     cache_len=33, q_chunk=q_chunk)
    want = _naive_attention(q, k, v, 0.35, cache, 33)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s0", [300, 301])
def test_mla_long_prompt_prefill_then_decode_matches_full_forward(s0):
    """MLA prefill takes 256-query chunks: a 300- or 301-token prompt splits
    into two chunks, the second padded at 301.  Prefill then four decode
    steps give the full forward's logits."""
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    s = s0 + 4
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, s), 0, cfg.vocab_size)
    h, _ = forward(params, {"tokens": toks}, cfg)
    full = np.asarray(L.lm_logits(params["embed"], h, cfg))
    logits, cache = prefill(params, {"tokens": toks[:, :s0]}, cfg,
                            init_cache(cfg, 1, s0 + 16))
    steps = [np.asarray(logits[:, 0])]
    for t in range(s0, s):
        logits, cache = decode_step(params, {"tokens": toks[:, t:t + 1]}, cfg,
                                    cache, jnp.int32(t))
        steps.append(np.asarray(logits[:, 0]))
    # the bf16 latent cache rounds 300 entries where the full forward keeps
    # them in f32: a little over the 16-token test's 2e-2 (0.025 here)
    np.testing.assert_allclose(full[:, s0 - 1:], np.stack(steps, axis=1),
                               rtol=4e-2, atol=4e-2)


def test_mla_train_step_at_odd_lengths():
    """Training takes 512-query chunks: at 300 tokens one chunk, at 601 two
    of 301 with one query of padding.  The loss and gradient at 300 are
    finite, and the attention layer's output at 601 tokens is, over the
    first 300 positions, its output at 300 (the whole model can differ
    there where a near-tie flips a token's experts)."""
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 601), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :300], "labels": toks[:, 1:301]}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in jax.tree_util.tree_leaves(grads))
    p, _ = L.init_mla(cfg, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 601, cfg.d_model)
                          ).astype(jnp.bfloat16)
    long, _ = L.mla_fwd(p, x, cfg, jnp.arange(601))
    short, _ = L.mla_fwd(p, x[:, :300], cfg, jnp.arange(300))
    np.testing.assert_allclose(np.asarray(long[:, :300], np.float32),
                               np.asarray(short, np.float32),
                               rtol=1e-2, atol=1e-2)


def _moe_case(n_experts=8, top_k=2, n_held=0, **kw):
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    cfg = replace(cfg, moe=replace(cfg.moe, n_experts=n_experts, top_k=top_k,
                                   n_held=n_held, **kw))
    p, axes = L.init_moe(cfg, jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 17, cfg.d_model)
                          ).astype(jnp.bfloat16)
    return cfg, p, axes, x


def test_moe_token_chunks_take_any_count(monkeypatch):
    """34 tokens in chunks of at most 7: five chunks of 7, the last padded
    with one token routed nowhere.  The layer's output is the one-chunk
    output."""
    cfg, p, _, x = _moe_case(n_held=4)
    whole, _ = L.apply_moe(p, x, cfg)
    monkeypatch.setattr(L, "MOE_TOKEN_CHUNK", 7)
    chunked, _ = L.apply_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(chunked, np.float32),
                               np.asarray(whole, np.float32), rtol=1e-2,
                               atol=1e-2 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("n_held", [0, 3])
def test_capacity_dispatch_with_room_for_every_pair_is_dropless(n_held):
    """The capacity dispatch (taken under an expert-split mesh) with a
    capacity that holds every pair gives the dropless layer's part, for
    all 8 experts held and for 3 of them (pairs on the other 5 add
    nothing); at a capacity of one pair per expert it drops some."""
    cfg, p, _, x = _moe_case(n_held=n_held, capacity_factor=4.0)
    xt = x.reshape(-1, cfg.d_model)
    logits = xt.astype(jnp.float32) @ p["router"]
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 2)
    want = L._dropless(p, xt, top_idx, top_w, cfg.moe)
    got = L._with_capacity(p, xt, top_idx, top_w, cfg.moe)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2,
                               atol=2e-2 * float(jnp.abs(want).max()))
    tight = replace(cfg.moe, capacity_factor=1 / 34)    # capacity 1
    dropped = L._with_capacity(p, xt, top_idx, top_w, tight)
    assert float(jnp.abs(dropped - want).max()) > 0.1 * float(jnp.abs(want).max())


def test_moe_under_an_expert_split_mesh():
    """On a 4-device mesh whose model axis splits the 8 experts, the layer
    takes the capacity dispatch.  With a capacity that holds every pair it
    gives the one-device dropless output, and no expert weight is gathered
    whole onto a device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import sharding as sh
    cfg, p, axes, x = _moe_case(capacity_factor=4.0)   # capacity = tokens
    want, _ = L.apply_moe(p, x, cfg)
    mesh = make_host_mesh(data=1, model=4)
    shardings = sh.param_shardings(p, axes, mesh, sh.ShardingPolicy())
    assert shardings["wi_gate"].spec[0] == "model"
    ps = jax.device_put(p, shardings)
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda p, x: L.apply_moe(p, x, cfg)[0])
        compiled = fn.lower(ps, x).compile()
        got = fn(ps, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2,
                               atol=2e-2 * float(jnp.abs(want).max()))
    hlo = compiled.as_text()
    assert "ragged" not in hlo
    whole = [f"[{','.join(map(str, w.shape))}]"
             for w in (p["wi_gate"], p["wi_up"], p["wo"])]
    gathers = [ln for ln in hlo.splitlines() if "all-gather" in ln]
    assert not [ln for ln in gathers if any(w in ln.split("all-gather")[0]
                                            for w in whole)], gathers
