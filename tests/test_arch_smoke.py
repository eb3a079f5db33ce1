"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs; plus prefill+decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import decode_step, init_cache, init_model, loss_fn, prefill

jax.config.update("jax_platform_name", "cpu")

B, S = 2, 64


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.frontend is not None:
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model),
                                            jnp.float32).astype(jnp.bfloat16)
        batch["tokens"] = jnp.zeros((B, S), jnp.int32)  # unused but present
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params, axes = init_model(cfg, key)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    loss, metrics = loss_fn(params, batch, cfg)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    assert float(metrics["ce"]) > 0

    # one grad step exists and is finite
    grads = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)), f"{arch}: grad norm not finite"
    assert float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_axes_tree_matches_params(arch):
    cfg = get_config(arch).reduced()
    params, axes = init_model(cfg, jax.random.PRNGKey(0))
    # axes uses tuples at leaf positions; compare structure by flattening
    # params and walking axes with the same key paths
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for kp, leaf in flat:
        node = axes
        ok = True
        for k in kp:
            key = getattr(k, "key", getattr(k, "idx", None))
            if isinstance(node, (list, tuple)) and not isinstance(key, int):
                ok = False
                break
            try:
                node = node[key]
            except (KeyError, IndexError, TypeError):
                ok = False
                break
        assert ok, f"{arch}: no axes entry for {jax.tree_util.keystr(kp)}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch).reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    max_len = 96
    cache = init_cache(cfg, B, max_len)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    logits, cache = prefill(params, batch, cfg, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: prefill NaN"

    step_batch = {k: (v[:, :1] if v.ndim >= 2 else v) for k, v in batch.items()}
    logits2, cache = decode_step(params, step_batch, cfg, cache, jnp.int32(S))
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2)).all(), f"{arch}: decode NaN"


@pytest.mark.parametrize("arch", [
    "stablelm-3b", "mamba2-130m", "deepseek-v2-lite-16b",
])
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode must reproduce the full-sequence forward logits
    (the strongest correctness check for cache handling)."""
    cfg = get_config(arch).reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    s = 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks,
             "loss_mask": jnp.ones((1, s), jnp.float32)}

    from repro.models.transformer import forward
    from repro.models import layers as L
    h, _ = forward(params, batch, cfg)
    full_logits = L.lm_logits(params["embed"],
                              L.apply_norm(params["final_norm"], h)
                              if False else h, cfg)
    # forward() already applies final_norm; recompute consistently:
    full_logits = L.lm_logits(params["embed"], h, cfg)

    cache = init_cache(cfg, 1, s)
    step_logits = []
    for t in range(s):
        sb = {"tokens": toks[:, t : t + 1]}
        lg, cache = decode_step(params, sb, cfg, cache, jnp.int32(t))
        step_logits.append(np.asarray(lg[:, 0]))
    step_logits = np.stack(step_logits, axis=1)
    # bf16 KV/latent caches and the bf16 attention-output boundary round
    # what the full path keeps in f32; MLA's absorbed decode keeps its
    # products in f32, as the expanded path's K and V are
    tol = 2e-2
    np.testing.assert_allclose(np.asarray(full_logits), step_logits,
                               rtol=tol, atol=tol)


def _attention_cache(cache):
    """The position-indexed leaves of a serve cache, [L, B, T, ...] each."""
    return jax.tree_util.tree_leaves(cache.get("kv", cache.get("mla")))


def _prefill_then_decode(cfg, params, toks, s0, max_len):
    """Prefill toks[:, :s0] into a fresh cache, then decode the rest
    teacher-forced; returns each step's logits and the cache after it."""
    logits, cache = prefill(params, {"tokens": toks[:, :s0]}, cfg,
                            init_cache(cfg, toks.shape[0], max_len))
    steps = [(logits, cache)]
    for t in range(s0, toks.shape[1]):
        logits, cache = decode_step(params, {"tokens": toks[:, t:t + 1]}, cfg,
                                    cache, jnp.int32(t))
        steps.append((logits, cache))
    return steps


@pytest.mark.parametrize("arch", [
    "stablelm-3b",             # the chip benchmark's decode model
    "chatglm3-6b",             # GQA: 4 query heads over 2 KV heads
])
def test_prefill_then_decode_matches_full_forward(arch):
    """The serving path as `Server.generate` runs it: prefill a prompt into
    a cache longer than the request, then decode teacher-forced.  Every
    step's logits match the full-sequence forward, and the KV cache holds
    what one prefill of the whole sequence writes below the last position
    and zeros from it on."""
    from repro.models import layers as L
    from repro.models.transformer import forward
    cfg = get_config(arch).reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    b, s0, s, max_len = 2, 8, 16, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    h, _ = forward(params, {"tokens": toks}, cfg)
    full_logits = np.asarray(L.lm_logits(params["embed"], h, cfg))

    steps = _prefill_then_decode(cfg, params, toks, s0, max_len)
    step_logits = np.stack([np.asarray(lg[:, 0]) for lg, _ in steps], axis=1)
    np.testing.assert_allclose(full_logits[:, s0 - 1:], step_logits,
                               rtol=2e-2, atol=2e-2)

    _, ref = prefill(params, {"tokens": toks}, cfg, init_cache(cfg, b, max_len))
    for got, want in zip(_attention_cache(steps[-1][1]), _attention_cache(ref)):
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        np.testing.assert_allclose(got[:, :, :s], want[:, :, :s],
                                   rtol=2e-2, atol=2e-2)
        assert not got[:, :, s:].any(), f"written past position {s - 1}"


@pytest.mark.parametrize("arch", [
    "stablelm-3b",
    "chatglm3-6b",
    "jamba-1.5-large-398b",    # hybrid blocks: one attention layer a block
    "deepseek-v3-671b",        # MLA latent cache; 3 unrolled dense prefix layers
])
def test_serve_steps_write_only_their_positions(arch):
    """Prefill writes positions 0..s0-1 of every layer's cache and nothing
    beyond; each decode step at position t writes position t and leaves
    every other entry of the cache as it was, bit for bit."""
    cfg = get_config(arch).reduced()
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    b, s0, s, max_len = 2, 8, 12, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    steps = _prefill_then_decode(cfg, params, toks, s0, max_len)
    caches = [[np.asarray(x.astype(jnp.float32)) for x in _attention_cache(c)]
              for _, c in steps]

    def written(leaf):   # [L, T]: does layer l hold anything at position t
        return np.abs(leaf).max(axis=(1, *range(3, leaf.ndim))) > 0

    for leaf in caches[0]:
        assert leaf.shape[2] == max_len
        assert written(leaf)[:, :s0].all(), "prefill left a position unwritten"
        assert not written(leaf)[:, s0:].any(), "prefill wrote past its prompt"
    for t, (before, after) in enumerate(zip(caches, caches[1:]), start=s0):
        for old, new in zip(before, after):
            assert written(new)[:, t].all(), f"step {t} left a layer unwritten"
            np.testing.assert_array_equal(np.delete(new, t, axis=2),
                                          np.delete(old, t, axis=2))
