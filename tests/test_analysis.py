"""Unit tests for the HLO analyzer and analytic model math that drive the
roofline (§Roofline correctness matters as much as model correctness)."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import analyze, shape_info
from repro.analysis.model_math import model_flops, param_counts
from repro.configs import TRAIN_4K, get_config


def _compile_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_shape_info_parses_tuples():
    b, arrs = shape_info("(f32[16,16]{1,0}, bf16[8]{0})")
    assert b == 16 * 16 * 4 + 8 * 2
    assert len(arrs) == 2


def test_dot_flops_exact():
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    hlo = _compile_hlo(lambda a, b: a @ b, x, w)
    r = analyze(hlo)
    assert r["flops"] == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_while_trip_count_multiplies_flops():
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(w):
        def body(c, _):
            return c @ w, None
        c, _ = jax.lax.scan(body, jnp.eye(32), None, length=10)
        return c.sum()

    hlo = _compile_hlo(f, w)
    r = analyze(hlo)
    # 10 iterations x 2*32^3
    assert r["flops"] == pytest.approx(10 * 2 * 32 ** 3, rel=0.05)


def test_nested_scan_multiplier():
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)

    def f(w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        c, _ = jax.lax.scan(outer, jnp.eye(16), None, length=3)
        return c.sum()

    hlo = _compile_hlo(f, w)
    r = analyze(hlo)
    assert r["flops"] == pytest.approx(12 * 2 * 16 ** 3, rel=0.05)


def test_collectives_counted_with_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    mesh = jax.make_mesh((4,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a):
        return jax.lax.with_sharding_constraint(a.sum(0), P())

    with jax.set_mesh(mesh):
        hlo = jax.jit(
            f, in_shardings=NamedSharding(mesh, P("data", None)),
        ).lower(x).compile().as_text()
    r = analyze(hlo)
    assert r["collective_bytes"] > 0


# ---------------------------------------------------------------------------
# analytic model math
# ---------------------------------------------------------------------------

KNOWN_SIZES = {  # published total/active parameter counts (billions)
    "deepseek-v3-671b": (671, 37.6),
    "jamba-1.5-large-398b": (398, 94),
    "deepseek-v2-lite-16b": (15.7, 2.7),
    "starcoder2-15b": (16, 16),
    "chatglm3-6b": (6.2, 6.2),
    "mamba2-130m": (0.13, 0.13),
}


@pytest.mark.parametrize("arch,expect", KNOWN_SIZES.items())
def test_param_counts_match_published(arch, expect):
    n = param_counts(get_config(arch))
    assert n["total"] / 1e9 == pytest.approx(expect[0], rel=0.12)
    assert n["active"] / 1e9 == pytest.approx(expect[1], rel=0.12)


def test_model_flops_train_rule():
    cfg = get_config("stablelm-3b")
    mf = model_flops(cfg, TRAIN_4K)
    tokens = TRAIN_4K.seq_len * TRAIN_4K.global_batch
    assert mf["model_flops"] == pytest.approx(6 * mf["n_active"] * tokens)
    assert mf["attention_flops"] > 0


def test_moe_active_less_than_total():
    cfg = get_config("deepseek-v3-671b")
    n = param_counts(cfg)
    assert n["active"] < n["total"] / 10
