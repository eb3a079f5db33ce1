"""Each cell's run at a size a CPU test run holds, without the look for a
chip: sound as the program stands, and `correct` false with the timed path
broken underneath in each way the cell can break.  The fp8 control, put
in the program's place, comes out not correct under the committed limits.
(Both cells run on one chip, so no exchange between chips can be left
out.)"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_small  # noqa: E402

TRAIN = "mamba2-130m.train-ckpt"
SERVE = "stablelm-3b.decode"
SEED = 3_000_000_019          # above 2**31, as a benchmark run's seed may be


def _plain_step(trainer):
    from repro.runtime.steps import make_train_step_fn
    return make_train_step_fn(trainer.cfg, trainer.opt_cfg)


def state_unchanged(trainer):
    import jax
    step = _plain_step(trainer)
    trainer.step_fn = jax.jit(lambda s, b: (s, step(s, b)[1]))


def half_batch(trainer):
    import jax
    step = _plain_step(trainer)

    def half(s, b):
        n = b["tokens"].shape[0] // 2
        return step(s, {k: v[:n] for k, v in b.items()})
    trainer.step_fn = jax.jit(half, donate_argnums=(0,))


def altered_token(server):
    decode = server._decode
    calls = {"n": 0}

    def wrong(p, c, b, pos):
        logits, cache = decode(p, c, b, pos)
        calls["n"] += 1
        if calls["n"] % 5 == 0:    # one step in five serves token 7 to all rows
            logits = logits.at[:, 0, 7].set(1e4)
        return logits, cache
    server._decode = wrong


@pytest.fixture(scope="module")
def train_sound():
    return bench_small.run_small(TRAIN, SEED, control=True)


@pytest.fixture(scope="module")
def serve_sound():
    return bench_small.run_small(SERVE, SEED, control=True)


def test_train_sound_run_is_correct(train_sound):
    assert train_sound.correct, [(c.name, c.value, c.limit)
                                 for c in train_sound.checks]
    assert train_sound.metrics["train_tokens_per_s"] > 0
    assert train_sound.ctx["samples"] > 0


@pytest.mark.parametrize("plant", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(plant):
    r = bench_small.run_small(TRAIN, SEED, plant=plant)
    assert not r.correct


def test_serve_sound_run_is_correct(serve_sound):
    r = serve_sound
    assert r.correct, [(c.name, c.value, c.limit) for c in r.checks]
    assert r.ctx["readings"]["checked_tokens"] >= 60
    assert r.attempted == r.ctx["requests"] and r.failed == 0


def test_serve_altered_token_is_not_correct():
    r = bench_small.run_small(SERVE, SEED, plant=altered_token)
    assert not r.correct


def test_train_control_reads_above_program(train_sound):
    prog = train_sound.ctx["readings"]
    for name in ("control", "half_batch"):
        alt = train_sound.ctx[name]
        assert not alt["correct"], (name, alt["readings"])
        assert any(alt["readings"][k] > prog[k] for k in alt["failed"])


def test_serve_control_reads_above_program(serve_sound):
    control = serve_sound.ctx["control"]
    assert not control["correct"], control["readings"]
    assert control["readings"]["served_logit_gap"] \
        > serve_sound.ctx["readings"]["served_logit_gap"]
