"""DeepSeek-V2's layer mechanics at small sizes on the CPU: YaRN's rotary
frequencies and softmax scale against hand-computed values, the MoE layer
dropping nothing however skewed the routing, blocked attention and the MoE
token chunks at any length, blocked attention scoring only the keys a query
chunk's group can see, and the MoE layer under an expert-split mesh."""
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
    (4096, 256, False),    # 16 chunks in 8 groups of two
    (2250, 256, False),    # 9 chunks of 250: one group of two, seven of one
    (1000, 256, True),     # 4 chunks of 250 after the cache's 33 entries
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


def test_blocked_attention_gradient_matches_naive():
    """The training path: 300 queries in 5 chunks of 60, each group scoring
    only its keys.  The gradients with respect to q, k and v are the naive
    attention's."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (2, 300, 4, 8))
    k = jax.random.normal(ks[1], (2, 300, 2, 8))
    v = jax.random.normal(ks[2], (2, 300, 2, 8))
    ct = jax.random.normal(ks[3], (2, 300, 4, 8))

    def grads(attend):
        return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * ct),
                        argnums=(0, 1, 2))(q, k, v)
    got = grads(lambda q, k, v: L.blocked_causal_attention(q, k, v, 0.35,
                                                           q_chunk=64))
    want = grads(lambda q, k, v: _naive_attention(q, k, v, 0.35))
    assert len(L.causal_key_groups(300, 64).groups) == 5
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sq,q_chunk", [
    (300, 512), (512, 512), (300, 256), (601, 512), (4096, 256),
    (4099, 256), (2250, 256), (4096, 512), (32768, 512), (7, 1)])
def test_key_groups_cover_every_chunk_once_within_sq(sq, q_chunk):
    chunk, groups, share = L.causal_key_groups(sq, q_chunk)
    nchunks = -(-sq // chunk)
    assert chunk <= q_chunk and nchunks * chunk - sq < chunk
    assert [first for first, _, _ in groups] == list(
        np.cumsum([0] + [n for _, n, _ in groups[:-1]]))
    assert sum(n for _, n, _ in groups) == nchunks
    assert len(groups) == min(nchunks, L.MAX_KEY_GROUPS)
    assert max(n for _, n, _ in groups) - min(n for _, n, _ in groups) <= 1
    for first, n, extent in groups:
        # the keys end at the group's last real query, never past sq
        assert extent == min(sq, (first + n) * chunk)
    assert groups[-1][2] == sq
    want = sum(n * chunk * e for _, n, e in groups) / (nchunks * chunk * sq)
    assert share == pytest.approx(want)


def test_key_groups_share_of_the_square():
    """16 chunks score (8+1)/16 of the square; one chunk scores all of it,
    in one group whose keys are all sq."""
    assert L.causal_key_groups(4096, 256).share == 0.5625 <= 0.57
    assert L.causal_key_groups(300, 512) == (300, ((0, 1, 300),), 1.0)
    assert L.prefill_score_share(get_config("deepseek-v2-lite"), 4096) == 0.5625
    assert L.prefill_score_share(get_config("stablelm-3b"), 512) == 1.0
    assert L.prefill_score_share(get_config("mamba2-130m"), 4096) == 1.0


def _dot_shapes(jaxpr):
    """The operand shapes of every dot_general in `jaxpr`, nested too."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            out.append([x.aval.shape for x in e.invars])
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _dot_shapes(sub)
    return out


@pytest.mark.parametrize("sq,q_chunk", [(4096, 256), (300, 512)])
def test_blocked_attention_scores_only_its_groups_keys(sq, q_chunk):
    """In the traced program each group is one loop over its chunks, and
    every product in it (scores and the weighted value sum) spans the
    group's key extent: at 16 chunks 512, 1024, ... and all sq only in the
    last group; at one chunk one loop over all sq keys."""
    groups = L.causal_key_groups(sq, q_chunk).groups
    q = jax.ShapeDtypeStruct((1, sq, 4, 8), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, sq, 2, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: L.blocked_causal_attention(
        q, k, v, 0.35, q_chunk=q_chunk))(q, kv, kv).jaxpr
    loops = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(loops) == len(groups)
    for (first, n, extent), loop in zip(groups, loops):
        assert loop.params["length"] == n
        dots = _dot_shapes(loop.params["jaxpr"].jaxpr)
        assert len(dots) == 2
        assert [s[1] for shapes in dots for s in shapes if len(s) == 4] == [
            extent, extent]
    extents = [e for _, _, e in groups]
    assert extents[-1] == sq and sq not in extents[:-1]
    if sq == 4096:
        assert extents == [512 * (i + 1) for i in range(8)]


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
