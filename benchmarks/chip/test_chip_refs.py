"""The plain float32 references against the program, at the program's own
reduced sizes on the CPU: the same weights from the same key, and the same
loss or logits up to the program's bfloat16 rounding."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_small  # noqa: E402

SPEC = H.benchmark_spec()


@pytest.fixture(scope="module")
def mamba2():
    c = bench_small.small_config("mamba2-130m")
    return c, H.program_config(c), H.load_reference(SPEC, "mamba2-130m")


@pytest.fixture(scope="module")
def stablelm():
    c = bench_small.small_config("stablelm-3b")
    return c, H.program_config(c), H.load_reference(SPEC, "stablelm-3b")


def _leaves_equal(a, b):
    import jax
    fa, ta = jax.tree_util.tree_flatten_with_path(a)
    fb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (ka, x), (_, y) in zip(fa, fb):
        assert x.dtype == y.dtype, jax.tree_util.keystr(ka)
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32),
                                      err_msg=jax.tree_util.keystr(ka))


def test_mamba2_reference_init_is_the_programs(mamba2):
    import jax

    from repro.models import init_model
    c, cfg, ref = mamba2
    key = H.key_from_seed(2**40 + 7)
    _leaves_equal(jax.jit(lambda k: init_model(cfg, k)[0])(key),
                  jax.jit(lambda k: ref.init_params(c, k))(key))


def test_mamba2_reference_loss_matches_program(mamba2):
    import jax
    import jax.numpy as jnp

    from repro.models import init_model, loss_fn
    c, cfg, ref = mamba2
    key = H.key_from_seed(11)
    params = init_model(cfg, key)[0]
    rng = np.random.default_rng(0)
    tok = rng.integers(1, c["vocab_size"], size=(2, 65)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tok[:, :-1]), "labels": jnp.asarray(tok[:, 1:]),
             "loss_mask": jnp.ones((2, 64), jnp.float32)}
    prog = float(jax.jit(lambda p, b: loss_fn(p, b, cfg)[0])(params, batch))
    want = float(ref.nll_sum(ref.init_params(c, key), batch["tokens"],
                             batch["labels"], c)) / batch["tokens"].size
    assert abs(prog - want) / want < 5e-3


def test_mamba2_ssd_minimal_matches_sequential_recurrence(mamba2):
    import jax
    import jax.numpy as jnp
    _, _, ref = mamba2
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    b, t, h, p, n = 2, 16, 3, 4, 5
    x = jax.random.normal(k[0], (b, t, h, p))
    a = -jax.random.uniform(k[1], (b, t, h))
    B = jax.random.normal(k[2], (b, t, h, n))
    C = jax.random.normal(k[3], (b, t, h, n))
    y = ref.ssd_minimal(x, a, B, C, 4)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        state = state * jnp.exp(a[:, i])[..., None, None] \
            + jnp.einsum("bhp,bhn->bhpn", x[:, i], B[:, i])
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, C[:, i]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(ys, 1)),
                               rtol=1e-4, atol=1e-4)


def test_stablelm_reference_init_is_the_programs(stablelm):
    import jax

    from repro.models import init_model
    c, cfg, ref = stablelm
    key = H.key_from_seed(5)
    params = jax.jit(lambda k: init_model(cfg, k)[0])(key)
    tok, head = ref.embed_weights(c, key)
    _same_up_to_rare_ulps(np.asarray(params["embed"]["tok"], np.float32),
                          np.asarray(tok), "tok")
    _same_up_to_rare_ulps(np.asarray(params["embed"]["head"], np.float32),
                          np.asarray(head), "head")
    for i in range(c["num_hidden_layers"]):
        w = ref.layer_weights(c, key, i)
        blk = jax.tree_util.tree_map(lambda x: x[i], params["blocks"])
        pairs = [("wq", blk["attn"]["wq"]), ("wk", blk["attn"]["wk"]),
                 ("wv", blk["attn"]["wv"]), ("wo", blk["attn"]["wo"]),
                 ("gate", blk["ffn"]["wi_gate"]), ("up", blk["ffn"]["wi_up"]),
                 ("down", blk["ffn"]["wo"])]
        for name, got in pairs:
            _same_up_to_rare_ulps(np.asarray(got, np.float32),
                                  np.asarray(w[name]), name)


def _same_up_to_rare_ulps(got, want, name):
    """Equal, but for a rare bfloat16 rounding flip where XLA fuses the
    init's scaling differently in another program (one ulp, under 1e-4 of
    the elements)."""
    diff = got != want
    assert diff.mean() < 1e-4, name
    np.testing.assert_allclose(got[diff], want[diff], rtol=2 ** -7, err_msg=name)


def test_stablelm_reference_logits_match_program(stablelm):
    import jax
    import jax.numpy as jnp

    from repro.models import init_model
    from repro.models import layers as L
    from repro.models.transformer import forward
    c, cfg, ref = stablelm
    key = H.key_from_seed(9)
    params = init_model(cfg, key)[0]
    rng = np.random.default_rng(1)
    seq = rng.integers(1, c["vocab_size"], size=(24,)).astype(np.int32)
    h, _ = forward(params, {"tokens": jnp.asarray(seq[None])}, cfg)
    prog = np.asarray(L.lm_logits(params["embed"], h, cfg))[0]
    want = ref.logits_at(c, key, [seq], [np.arange(24)])[0]
    scale = want.std()
    assert np.max(np.abs(prog - want)) < 0.05 * scale
    assert (prog.argmax(-1) == want.argmax(-1)).mean() > 0.9
