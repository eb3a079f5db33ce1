"""The DeepSeek-V2-Lite chip share against its plain float32 reference, on
the CPU at a small size with seeded random weights: the same weights from
the same key, prefill then decode through the cache against the reference's
full forward pass, the expert shares of a layer adding up to the uncut
layer, YaRN as the configuration file states it, the counts against a hand
count, and the cell's run sound, with the fp8 control and a served token
altered coming out not correct."""
import copy
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_small  # noqa: E402

SPEC = H.benchmark_spec()
CONFIG = "deepseek-v2-lite"
CELL = "deepseek-v2-lite.rag-decode"
# every width cut, the published 6 experts a token and 2 shared experts
# kept; 2 of the router's 16 experts held: the 8-way share, as on the chip
SMALL = {"hidden_size": 128, "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 256, "vocab_size": 512,
         "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
         "v_head_dim": 32, "moe_intermediate_size": 64, "router_experts": 16,
         "n_routed_experts": 2}
SEED = 3_000_000_019          # above 2**31, as a benchmark run's seed may be


def _set(obj, path, val):
    head, *rest = path
    return replace(obj, **{head: _set(getattr(obj, head), rest, val)
                           if rest else val})


def program_cfg(c):
    """The program's config for a configuration dict: the registry entry
    with every mapped field set from the dict."""
    from repro.configs import get_config
    cfg = get_config(c["program"]["arch"]).reduced()
    for key, path in c["program"]["fields"].items():
        cfg = _set(cfg, path.split("."), c[key])
    return cfg


def small_config(**over):
    c = copy.deepcopy(H.load_config(SPEC, CONFIG))
    c.update(SMALL, **over)
    return c


@pytest.fixture(scope="module")
def small():
    c = small_config()
    return c, program_cfg(c), H.load_reference(SPEC, CONFIG)


def _same_up_to_rare_ulps(got, want, name):
    """Equal, but for a rare bfloat16 rounding flip where XLA fuses the
    init's scaling differently in another program (one ulp, under 1e-3 of
    the elements)."""
    diff = got != want
    assert diff.mean() < 1e-3, name
    np.testing.assert_allclose(got[diff], want[diff], rtol=2 ** -7, err_msg=name)


def test_program_config_is_the_files():
    c = H.load_config(SPEC, CONFIG)
    cfg = H.program_config(c)
    rs = c["rope_scaling"]
    assert (cfg.yarn.factor, cfg.yarn.original_max_position, cfg.yarn.beta_fast,
            cfg.yarn.beta_slow, cfg.yarn.mscale, cfg.yarn.mscale_all_dim) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert cfg.moe.n_experts == c["published"]["n_routed_experts"] == 64
    assert cfg.moe.held == 8 and cfg.moe.router == "softmax"


def test_yarn_matches_reference_at_published_dims():
    import jax.numpy as jnp

    from repro.models import layers as L
    c = H.load_config(SPEC, CONFIG)
    cfg = H.program_config(c)
    ref = H.load_reference(SPEC, CONFIG)
    np.testing.assert_allclose(np.asarray(L.rope_freqs(64, 10000.0, cfg.yarn)),
                               ref.yarn_inv_freq(c), rtol=1e-6)
    assert L.mla_softmax_scale(cfg) == pytest.approx(ref.softmax_scale(c), rel=1e-12)
    assert ref.yarn_cos_sin_scale(c) == 1.0
    x = jnp.ones((1, 3, 1, 64), jnp.float32)
    y = L.apply_rope(x, jnp.arange(3), 10000.0, yarn=cfg.yarn)
    np.testing.assert_allclose(np.asarray(y)[0, 0], 1.0)   # position 0: no turn


def test_reference_init_is_the_programs(small):
    import jax

    from repro.models import init_model
    c, cfg, ref = small
    key = H.key_from_seed(SEED)
    params = jax.jit(lambda k: init_model(cfg, k)[0])(key)
    tok, head = ref.embed_weights(c, key)
    _same_up_to_rare_ulps(np.asarray(params["embed"]["tok"], np.float32),
                          np.asarray(tok), "tok")
    _same_up_to_rare_ulps(np.asarray(params["embed"]["head"], np.float32),
                          np.asarray(head), "head")
    pairs = {"attn": [("wq", "wq"), ("wkv_a", "wkv_a"), ("wk_b", "wk_b"),
                      ("wv_b", "wv_b"), ("wo", "wo")]}
    for i in range(c["num_hidden_layers"]):
        w = ref.layer_weights(c, key, i)
        if i < c["first_k_dense_replace"]:
            lp = params["prefix"][i]
            ffn = [(lp["ffn"][a], w["mlp"][b]) for a, b in
                   (("wi_gate", "gate"), ("wi_up", "up"), ("wo", "down"))]
        else:
            lp = jax.tree_util.tree_map(lambda x: x[i - 1], params["blocks"])
            f, m = lp["ffn"], w["moe"]
            np.testing.assert_allclose(np.asarray(f["router"]),
                                       np.asarray(m["router"]), rtol=1e-6)
            ffn = [(f["wi_gate"], m["gate"]), (f["wi_up"], m["up"]),
                   (f["wo"], m["down"]),
                   (f["shared"]["wi_gate"], m["shared"]["gate"]),
                   (f["shared"]["wi_up"], m["shared"]["up"]),
                   (f["shared"]["wo"], m["shared"]["down"])]
        for a, b in pairs["attn"]:
            _same_up_to_rare_ulps(np.asarray(lp["attn"][a], np.float32),
                                  np.asarray(w["attn"][b]), f"{i}.{a}")
        for j, (got, want) in enumerate(ffn):
            _same_up_to_rare_ulps(np.asarray(got, np.float32), np.asarray(want),
                                  f"{i}.ffn{j}")


def test_prefill_then_decode_matches_reference(small):
    """The serving path as `Server.generate` runs it, against the
    reference's full forward pass: bfloat16 weights, activations and cache
    against float32 keep every logit within a tenth of the logits' spread
    (measured 0.054-0.067 over 4 seeds) and the argmax nearly everywhere."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step, init_cache, init_model, prefill
    c, cfg, ref = small
    key = H.key_from_seed(SEED)
    params = jax.jit(lambda k: init_model(cfg, k)[0])(key)
    b, s0, s, max_len = 2, 12, 20, 24
    toks = np.random.default_rng(5).integers(1, c["vocab_size"], size=(b, s)
                                             ).astype(np.int32)
    logits, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :s0])}, cfg,
                            init_cache(cfg, b, max_len))
    got = [np.asarray(logits[:, 0])]
    for t in range(s0, s):
        logits, cache = decode_step(params, {"tokens": jnp.asarray(toks[:, t:t + 1])},
                                    cfg, cache, jnp.int32(t))
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got, 1)
    want = ref.logits_at(c, key, list(toks), [np.arange(s0 - 1, s)] * b)[0]
    want = want.reshape(got.shape)
    assert np.max(np.abs(got - want)) < 0.1 * want.std()
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.85


def test_expert_shares_add_up_to_the_uncut_layer(small):
    """Eight chips' shares of one MoE layer, each routing over all experts
    and computing its own two, add up to the reference's uncut layer once
    the shared experts, which every chip computes, are counted once.  A
    chip holds the first experts of its router's outputs, so share r is
    the layer with the router's columns rotated by r shares."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L
    c, cfg, ref = small
    e, n = c["router_experts"], c["n_routed_experts"]
    c_all = small_config(n_routed_experts=e)
    w = ref.layer_weights(c_all, H.key_from_seed(SEED), 1)["moe"]
    # float32 activations (of bfloat16 values) keep each share's sum out of
    # bfloat16, so a share's rounding cannot hide an expert's part
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, c["hidden_size"])
                          ).astype(jnp.bfloat16).astype(jnp.float32)
    weight, _ = ref.routing(x, w, c_all)
    want = ref._swiglu(x, w["shared"], "f32")
    for j in range(e):
        ew = {k: w[k][j] for k in ("gate", "up", "down")}
        want = want + ref._swiglu(x, ew, "f32") * weight[..., j:j + 1]
    bf = jnp.bfloat16
    shared = {"wi_gate": w["shared"]["gate"].astype(bf),
              "wi_up": w["shared"]["up"].astype(bf),
              "wo": w["shared"]["down"].astype(bf)}
    total = -(e // n - 1) * L.apply_mlp(shared, x, cfg)
    for r in range(e // n):
        p = {"router": jnp.roll(w["router"], -r * n, axis=1), "shared": shared,
             "wi_gate": w["gate"][r * n:(r + 1) * n].astype(bf),
             "wi_up": w["up"][r * n:(r + 1) * n].astype(bf),
             "wo": w["down"][r * n:(r + 1) * n].astype(bf)}
        total = total + L.apply_moe(p, x, cfg)[0]
    routed = want - ref._swiglu(x, w["shared"], "f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=0.01 * float(routed.std()))


def test_counts_hand_count():
    c = H.load_config(SPEC, CONFIG)
    counts = H.load_flops(SPEC, CONFIG)
    d, h, r, rope, V = 2048, 16, 512, 64, 102400
    attn_w = d * h * 192 + d * 576 + 2 * r * h * 128 + h * 128 * d   # 13,762,560
    expert_w = 3 * d * 1408                                          # 8,650,752
    assert counts.attn_weights(c) == attn_w == 13_762_560
    # dense layer, 26 MoE layers (router 64, 8 held, 2 shared), head
    n = 27 * (attn_w + 2 * d + r) + 3 * d * 10944 \
        + 26 * (d * 64 + 10 * expert_w) + 2 * V * d + d
    assert counts.param_count(c) == n == 3_110_989_312
    assert counts.param_count(dict(c, n_routed_experts=64)) == pytest.approx(
        15.7e9, rel=0.001)
    pos = [4095, 4200]
    hit = 8 * (1 - (1 - 6 / 64) ** 2)
    entries = 4096 + 4201
    attn = counts.mla_decode_attn(c, pos)
    assert attn["flops"] == pytest.approx(2 * 27 * h * (2 * r + rope) * entries)
    assert attn["bytes"] == pytest.approx(27 * entries * 576 * 2
                                          + 27 * 2 * h * (2 * r + rope) * 4)
    moe = counts.moe_experts(c, 2)
    assert moe["flops"] == pytest.approx(2 * 26 * (2 * 6 * 8 / 64) * expert_w)
    assert moe["bytes"] == pytest.approx(
        26 * (hit * expert_w * 2 + 2 * (2 * 6 * 8 / 64) * d * 2))
    step = counts.decode_step(c, pos)
    per_token = 27 * attn_w + 3 * d * 10944 \
        + 26 * (d * 64 + 2 * expert_w) + d * V
    assert step["flops"] == pytest.approx(2 * per_token * 2 + attn["flops"]
                                          + moe["flops"])
    weights = 2 * (27 * (attn_w + 2 * d + r) + 3 * d * 10944
                   + 26 * 2 * expert_w + d * V + d) + 4 * 26 * d * 64
    cache = (entries + 2) * 27 * 576 * 2
    assert step["bytes"] == pytest.approx(
        weights + 2 * d * 2 + cache + 26 * hit * expert_w * 2)


def test_param_count_matches_program():
    import jax

    from repro.runtime.steps import abstract_params
    c = H.load_config(SPEC, CONFIG)
    n = sum(x.size for x in jax.tree_util.tree_leaves(
        abstract_params(H.program_config(c))))
    assert H.load_flops(SPEC, CONFIG).param_count(c) == n


# ---------------------------------------------------------------------------
# the cell's run, small
# ---------------------------------------------------------------------------

def _run(seed, *, plant=None, control=False):
    tr = copy.deepcopy(H.load_traffic(H.find_cell(SPEC, CELL)["traffic"]))
    tr.update(batch=3, max_len=24, prompt_buckets=[16], max_new_tokens=6,
              nominal_batch_s=0.25, warmup_new_tokens=2, check_min_tokens=60)
    c = small_config()
    c["program"]["reduced"] = True
    args = H.RunArgs(
        workload=CELL, seed=seed, seconds=2.0, trace=False, config=c,
        traffic=tr, limits=H.load_checks(CELL),
        reference=H.load_reference(SPEC, CONFIG),
        devices=[bench_small.FakeDevice()], t_start=time.perf_counter(),
        plant=plant, control=control)
    return H.load_runner(tr["kind"]).run(args)


@pytest.fixture(scope="module")
def sound(monkeypatch_module):
    return _run(SEED, control=True)


@pytest.fixture(scope="module")
def monkeypatch_module():
    """The small config in place of the registry's reduced one, for the
    runner's `Server` (which builds the arch's reduced config itself)."""
    from repro import configs
    mp = pytest.MonkeyPatch()
    small_cfg = program_cfg(small_config())
    reduced = type(small_cfg).reduced
    mp.setattr(type(small_cfg), "reduced",
               lambda self, **kw: small_cfg if self.name == CONFIG
               else reduced(self, **kw))
    assert configs.get_config(CONFIG).reduced() is small_cfg
    yield mp
    mp.undo()


def test_serve_sound_run_is_correct(sound):
    assert sound.correct, [(c.name, c.value, c.limit) for c in sound.checks]
    assert sound.ctx["readings"]["checked_tokens"] >= 60
    assert sound.attempted == sound.ctx["requests"] and sound.failed == 0


def test_serve_control_is_not_correct(sound):
    control = sound.ctx["control"]
    assert not control["correct"], control["readings"]
    assert control["readings"]["served_logit_gap"] \
        > sound.ctx["readings"]["served_logit_gap"]


def test_serve_altered_token_is_not_correct(monkeypatch_module):
    def altered(server):
        decode = server._decode
        calls = {"n": 0}

        def wrong(p, c, b, pos):
            logits, cache = decode(p, c, b, pos)
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                logits = logits.at[:, 0, 7].set(1e4)
            return logits, cache
        server._decode = wrong
    assert not _run(SEED, plant=altered).correct


def test_window_holds_enough_batches():
    runner = H.load_runner("serve")
    tr = H.load_traffic(H.find_cell(SPEC, CELL)["traffic"])
    deck = runner.request_deck(tr, SPEC["run_seconds"])
    assert len(deck) >= 4
    assert all(q["prompt"] == 4096 for q in deck)
    assert all(q["prompt"] + max(q["answers"]) <= tr["max_len"] for q in deck)
    assert math.isclose(sum(len(q["answers"]) for q in deck) / len(deck), 16)


def test_scope_of_reads_framework_names_and_kernels():
    import bench_scopes
    op = "jit(<lambda>)/while/body/closed_call/moe.experts/gather"
    assert bench_scopes.scope_of("fusion.3", op) == "moe.experts"
    assert bench_scopes.scope_of("fusion.4", "jit(f)/mla.attend") == "mla.attend"
    assert bench_scopes.scope_of("ragged-dot-none.1", "ragged-dot-none") \
        == "moe.experts"
    assert bench_scopes.scope_of("fusion.5", "jit(f)/xmla.attend/add") is None
    assert bench_scopes.scope_of("fusion.6", None) is None


def test_program_op_names_from_a_recorded_trace(tmp_path):
    """A program's optimized HLO, as the trace's metadata plane holds it,
    names the scope of each instruction."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    import bench_scopes
    import bench_trace

    @jax.jit
    def f(x):
        with jax.named_scope("mla.attend"):
            y = jnp.sin(x) @ x
        return y.sum()
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    xplane = bench_trace.newest_xplane(tmp_path)
    pids = {int(bench_trace._stat(ev, "program_id"))
            for plane in ProfileData.from_file(str(xplane)).planes
            for line in plane.lines for ev in line.events
            if bench_trace._stat(ev, "hlo_module") == "jit_f"}
    instrs = []
    for pid in pids:
        instrs += bench_scopes.program_instructions(xplane, f"jit_f({pid})")
    scoped = {n for n, s in bench_scopes.instruction_scopes(instrs).items()
              if s == "mla.attend"}
    assert scoped, instrs
    names = {n for n, _, _ in instrs}
    assert any(ops for _, _, ops in instrs), instrs
    assert all(set(ops) <= names for _, _, ops in instrs), instrs
    assert bench_scopes.program_instructions(xplane, "jit_nothing(0)") == []


def test_kernel_operands_take_the_kernels_scope():
    """The copies of a layer's expert weights that only feed the grouped
    products count towards `moe.experts`; an operand with a scope of its
    own keeps it, and operands of other ops take no scope."""
    import bench_scopes
    body = "jit(<lambda>)/while/body"
    instrs = [
        ("fusion.333", f"{body}/closed_call/moe.experts/gather", ["p.1"]),
        ("dynamic-slice_bitcast_fusion.6", f"{body}/squeeze", ["p.2"]),
        ("fusion.9", f"{body}/closed_call/mla.attend/dot", ["p.3"]),
        ("ragged-dot-none", "ragged-dot-none",
         ["fusion.333", "dynamic-slice_bitcast_fusion.6", "fusion.9"]),
        ("fusion.10", f"{body}/add", ["ragged-dot-none", "p.4"]),
    ]
    assert bench_scopes.instruction_scopes(instrs) == {
        "fusion.333": "moe.experts",
        "dynamic-slice_bitcast_fusion.6": "moe.experts",
        "fusion.9": "mla.attend",
        "ragged-dot-none": "moe.experts"}


def test_plant_moe_layer_mode_sees_each_fault(monkeypatch):
    """plant_moe.py --layer at a small size: the sound layer reads a
    bfloat16 rounding off the float32 reference, and each planted fault
    (capacity at the one-token calls, the held part zeroed, the groups
    rotated) reads far more."""
    import plant_moe
    c = small_config()
    cfg = program_cfg(c)
    tr = copy.deepcopy(H.load_traffic(H.find_cell(SPEC, CELL)["traffic"]))
    tr.update(batch=3, prompt_buckets=[80])
    monkeypatch.setattr(H, "load_config", lambda spec, name: c)
    monkeypatch.setattr(H, "program_config", lambda cj: cfg)
    monkeypatch.setattr(H, "load_traffic", lambda name: tr)
    restore, planted = plant_moe.faults()
    rows = {r["fault"]: r for r in plant_moe.layer_gaps(
        SPEC, H.find_cell(SPEC, CELL), SEED, planted, restore,
        ["sound", *planted])}
    assert rows["sound"]["decode_rel_gap"] < 0.02
    assert rows["sound"]["prefill_rel_gap"] < 0.02
    for name in planted:
        assert rows[name]["decode_rel_gap"] > 0.1, rows[name]
    assert rows["zero_held"]["prefill_rel_gap"] == 1.0
