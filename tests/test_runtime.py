"""Runtime tests: sharding rules, optimizer, compression, pipeline-parallel,
end-to-end trainer convergence + crash/restart."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import TRAIN_4K, get_config
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig, adamw_update, init_opt_state, lr_at
from repro.runtime import sharding as sh
from repro.runtime.steps import model_axes, abstract_params


def _mesh2x2():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (run under XLA_FLAGS host device count)")
    return make_host_mesh(2, 2)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=200)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params, cfg)
    def loss(p):
        return jnp.sum(p["w"] ** 2)
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    assert float(loss(params)) < 1e-2


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(lr_at(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0, rel=0.1)
    assert float(lr_at(cfg, jnp.int32(100))) == pytest.approx(0.1, rel=0.01)


def test_adamw_bf16_moments():
    cfg = AdamWConfig(moment_dtype=jnp.bfloat16, warmup_steps=1)
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    opt = init_opt_state(params, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    p2, opt2, m = adamw_update(g, opt, params, cfg)
    assert p2["w"].dtype == jnp.bfloat16
    assert float(m["grad_norm"]) == pytest.approx(4.0, rel=1e-2)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_basic_rules():
    mesh = _mesh2x2()
    cfg = get_config("stablelm-3b")
    params = abstract_params(cfg)
    axes = model_axes(cfg)
    specs = sh.param_specs(params, axes, mesh, sh.ShardingPolicy())
    # embedding [vocab, d]: vocab->model, d->data (FSDP)
    assert specs["embed"]["tok"] == P("model", "data")
    # stacked attention wq [L, d, H, dh]: layer dim replicated
    assert specs["blocks"]["attn"]["wq"][0] is None
    assert "model" in str(specs["blocks"]["attn"]["wq"])


def test_param_specs_nondivisible_replicates():
    mesh = _mesh2x2()
    spec = sh.spec_for(("embed", "kv_heads", "head_dim"), (128, 3, 64), mesh,
                       sh.ShardingPolicy())
    padded = tuple(spec) + (None,) * 3
    assert padded[1] is None  # 3 kv heads % 2 != 0 -> replicated


def test_batch_spec_sp_fallback():
    mesh = _mesh2x2()
    assert sh.batch_spec(mesh, 8, 128) == P(("data",), None)
    # batch=1: sequence sharding fallback
    assert sh.batch_spec(mesh, 1, 128) == P(None, ("data",))


def test_activation_spec_train_uses_model_axis():
    mesh = _mesh2x2()
    spec = sh.activation_spec_for(mesh, TRAIN_4K)
    assert spec == P(("data",), "model", None)


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_compressed_psum_tree_accuracy():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from repro.runtime.compression import compressed_psum_tree
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    g = {"w": jnp.linspace(-1.0, 1.0, 512).reshape(2, 256)}
    with jax.set_mesh(mesh):
        out = compressed_psum_tree(g, mesh, axis="pod")
    # replicated input: mean over pod = identity (up to int8 quantization)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=2e-2)


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

def test_pipeline_forward_matches_sequential():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from repro.runtime.pipeline_par import bubble_fraction, pipeline_forward
    mesh = jax.make_mesh((4,), ("pod",))
    s_stages, b, d = 4, 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (s_stages, d, d)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d))

    def layer_fn(w, h):
        return jnp.tanh(h @ w)
    ref = x
    for i in range(s_stages):
        ref = layer_fn(ws[i], ref)

    with jax.set_mesh(mesh):
        out = pipeline_forward(layer_fn, ws, x, mesh=mesh, axis="pod",
                               n_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)


# ---------------------------------------------------------------------------
# trainer end-to-end: loss decreases; crash/restart resumes
# ---------------------------------------------------------------------------

def test_trainer_loss_decreases(tmp_path):
    from repro.launch.train import Trainer, TrainerConfig
    tc = TrainerConfig(arch="stablelm-3b", steps=30, global_batch=4,
                       seq_len=32, lr=1e-3, ckpt_every=100, log_every=30,
                       data_dir=str(tmp_path), n_servers=2)
    rng = np.random.default_rng(0)
    # learnable corpus: repeated short patterns
    corpus = [np.tile(rng.integers(1, 64, size=8), 5).astype(np.uint32)
              for _ in range(64)]
    tr = Trainer(tc, corpus=corpus)
    tr.init_or_restore()
    batch = next(iter(tr.pipeline))
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    state_copy = jax.tree_util.tree_map(lambda x: x.copy(), tr.state)
    _, m0 = tr.step_fn(state_copy, jb)  # step_fn donates arg 0: copy it
    first_loss = float(m0["loss"])
    out = tr.run()
    assert out["final_loss"] < first_loss, (first_loss, out)
    tr.shutdown()


def test_trainer_crash_restart_resumes(tmp_path):
    """Kill before the last checkpoint commits, resume from the one before,
    and finish on exactly the loss of the uninterrupted run."""
    from repro.launch.train import Trainer, TrainerConfig
    tc = TrainerConfig(arch="mamba2-130m", steps=6, global_batch=4,
                       seq_len=64, ckpt_every=3, log_every=1,
                       data_dir=str(tmp_path), n_servers=2, run_name="cr")
    tr = Trainer(tc)
    full = tr.run()   # commits checkpoints at steps 3 and 6
    # the job dies before step 6 commits: without its MANIFEST the step is
    # invisible (atomic commit)
    tr.lib.unlink(f"{tr.ckpt._step_dir(6)}/MANIFEST")
    tr.shutdown()

    tr2 = Trainer(tc)
    tr2.init_or_restore()
    assert tr2.start_step == 3
    assert tr2.sampler.step == 3  # batches consumed, not the prefetch cursor
    resumed = tr2.run()           # steps 4-6
    tr2.shutdown()
    assert sorted(resumed["losses"]) == [4, 5, 6]
    assert all(np.isfinite(v) for v in full["losses"].values())
    assert resumed["final_loss"] == pytest.approx(full["final_loss"], rel=1e-6)


def test_trainer_restore_missing_leaf_fails_loudly(tmp_path):
    from repro.launch.train import Trainer, TrainerConfig
    tc = TrainerConfig(arch="mamba2-130m", steps=2, global_batch=2,
                       seq_len=32, data_dir=str(tmp_path), n_servers=2)
    tr = Trainer(tc)
    try:
        tr.init_or_restore()
        partial = {"params": tr.state["params"]}  # optimizer state missing
        tr.ckpt.save(1, partial, extra={"train_step": 1,
                                        "sampler": tr.pipeline.state_dict()})
        with pytest.raises(KeyError, match="checkpoint missing leaf"):
            tr.init_or_restore()
    finally:
        tr.shutdown()


def test_compile_cache_dir_is_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the launchers pin
    the cache to <repo>/.jax_cache, never a per-run path."""
    from pathlib import Path
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo_cache = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert use_compile_cache() == repo_cache
        assert jax.config.jax_compilation_cache_dir == repo_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
