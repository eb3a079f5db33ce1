"""The chip benchmark's yardstick on the CPU: its files are found by name,
the counts match hand counts, the peaks refuse an unknown chip, the trace
reduction reads a small recorded trace right, and a run without a TPU
exits non-zero with no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_flops  # noqa: E402
import bench_harness as H  # noqa: E402
import bench_peaks  # noqa: E402
import bench_trace  # noqa: E402

SPEC = H.benchmark_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]


# ---------------------------------------------------------------------------
# every cell, configuration, mix and metric is found by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_found_by_name(workload):
    cell = H.find_cell(SPEC, workload)
    cfg = H.load_config(SPEC, cell["config"])
    assert cfg["name"] == cell["config"]
    ref = H.load_reference(SPEC, cell["config"])
    tr = H.load_traffic(cell["traffic"])
    runner = H.load_runner(tr["kind"])
    assert callable(runner.run)
    limits = H.load_checks(workload)["limits"]
    assert limits and all("limit" in v for v in limits.values())
    assert hasattr(ref, "train_readings") or hasattr(ref, "served_gaps")


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_counts_found_by_name(config):
    counts = H.load_flops(SPEC, config)
    assert hasattr(counts, "train_step_flops") or hasattr(counts, "decode_step")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_layer_metric_found_by_name(metric):
    reader = H.load_module(HERE / "layer_metrics" / f"{metric}.py")
    assert reader.read({}, {"kind": "TPU v5 lite"}) is None  # nothing to read


@pytest.mark.parametrize("workload", CELLS)
def test_each_cell_reports_setup_and_metrics(workload):
    e2e = [m["name"] for m in H.metrics_for(SPEC, workload, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert H.metrics_for(SPEC, workload, "per_layer")


def test_decode_deck_is_the_same_work_for_every_seed():
    runner = H.load_runner("serve")
    tr = H.load_traffic("decode")
    deck = runner.request_deck(tr, 45)
    assert sum(len(q["answers"]) for q in deck) >= 24
    assert all(max(q["answers"]) <= tr["max_new_tokens"] for q in deck)
    assert all(q["prompt"] in tr["prompt_buckets"] for q in deck)

    def work(d):
        return sorted((q["prompt"], sorted(q["answers"])) for q in d)
    orders = [runner.seeded_order(s, deck) for s in (1, 2, 2**33 + 9)]
    assert all(work(o) == work(deck) for o in orders)
    assert len({tuple(tuple(q["answers"]) for q in o) for o in orders}) > 1


def test_lognormal_quantiles_keep_the_source_mean():
    runner = H.load_runner("serve")
    q = runner.lognormal_quantiles({"mean": 214.5, "sd": 161.8}, 4000)
    assert sum(q) / len(q) == pytest.approx(214.5, rel=0.02)


def test_unknown_workload_is_an_error():
    with pytest.raises(H.BenchError):
        H.find_cell(SPEC, "no-such.cell")


def test_program_config_matches_files():
    for c in SPEC["configs"]:
        cfg = H.program_config(H.load_config(SPEC, c["name"]))
        assert cfg.name == c["name"]


def test_program_config_refuses_a_drifted_file():
    cfg = H.load_config(SPEC, "mamba2-130m")
    cfg["d_model"] = 1024
    with pytest.raises(H.BenchError):
        H.program_config(cfg)


# ---------------------------------------------------------------------------
# peaks and counts
# ---------------------------------------------------------------------------

def test_peaks_known_chip():
    p = bench_peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_peaks_unknown_chip_is_refused():
    with pytest.raises(KeyError):
        bench_peaks.peaks_for("TPU v9 imaginary")


def test_mamba2_flops_hand_count():
    c = H.load_config(SPEC, "mamba2-130m")
    # d 768, di 1536, H 24, P 64, N 128, G 1, W 4, L 256, V 50280
    in_proj = 2 * 768 * (2 * 1536 + 2 * 128 + 24)          # 5,148,672
    conv = 2 * 4 * (1536 + 256)                              # 14,336
    cb = 128 * 257                                           # 32,896
    intra = 24 * 64 * 257                                    # 394,752
    state = inter = 2 * 24 * 64 * 128                        # 393,216 each
    pass_ = 2 * 24 * 64 * 128 / 256                          # 1,536
    out_proj = 2 * 1536 * 768                                # 2,359,296
    layer = in_proj + conv + cb + intra + state + inter + pass_ + out_proj
    head = 2 * 768 * 50280                                   # 77,230,080
    per_token = 24 * layer + head
    assert bench_flops.mamba2_fwd_flops_per_token(c) == pytest.approx(per_token)
    assert bench_flops.mamba2_train_step_flops(c, 32, 2048) == pytest.approx(
        3 * per_token * 32 * 2048)
    assert per_token == pytest.approx(2.868e8, rel=1e-3)


def test_mamba2_param_count_matches_program():
    import jax

    from repro.runtime.steps import abstract_params
    c = H.load_config(SPEC, "mamba2-130m")
    cfg = H.program_config(c)
    n = sum(x.size for x in jax.tree_util.tree_leaves(abstract_params(cfg)))
    assert bench_flops.mamba2_param_count(c) == n


def test_stablelm_decode_hand_count():
    c = H.load_config(SPEC, "stablelm-3b")
    d, ff, V, nl = 2560, 6912, 50304, 32
    per_layer_mm = 4 * d * d + 3 * d * ff                    # 79,282,176
    flops_tok = 2 * (nl * per_layer_mm + d * V)
    pos = [127, 511]
    attn = sum(nl * 4 * 32 * 80 * (p + 1) for p in pos)
    layer_w = per_layer_mm + 4 * d
    weights = 2 * (nl * layer_w + 2 * d + d * V)
    kv_entry = 2 * nl * 32 * 80 * 2                          # 327,680 bytes
    kv = sum((p + 1) * kv_entry for p in pos) + 2 * kv_entry
    got = bench_flops.stablelm_decode_step(c, pos)
    assert got["flops"] == pytest.approx(2 * flops_tok + attn)
    assert got["bytes"] == pytest.approx(weights + 2 * 2 * d + kv)
    assert kv_entry == 327680


def test_stablelm_param_count_matches_program():
    import jax

    from repro.runtime.steps import abstract_params
    c = H.load_config(SPEC, "stablelm-3b")
    n = sum(x.size for x in jax.tree_util.tree_leaves(
        abstract_params(H.program_config(c))))
    assert bench_flops.stablelm_param_count(c) == n


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _ev(line, name, start, dur, plane="/device:TPU:0", pid=None):
    e = {"plane": plane, "line": line, "name": name, "start_ns": float(start),
         "dur_ns": float(dur)}
    if pid is not None:
        e["program_id"] = pid
    return e


def test_reduce_hand_made_trace():
    host = "/host:CPU"
    events = [
        _ev("python", "bench.window", 100, 1000, plane=host),
        _ev("python", "bench.batch_wait", 150, 100, plane=host),
        _ev("python", "bench.ckpt_save", 600, 300, plane=host),
        _ev("XLA Modules", "jit_step(7)", 250, 300, pid=7),
        _ev("XLA Modules", "jit_step(7)", 950, 100, pid=7),
        _ev("XLA Ops", "fusion.1", 250, 200),
        _ev("XLA Ops", "convolution.2", 450, 100),     # right after fusion.1
        _ev("XLA Ops", "fusion.1", 950, 100),
        _ev("XLA Ops", "copy.3", 50, 100),             # half outside the window
    ]
    r = bench_trace.reduce_events(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,150] + [250,550] + [950,1050] = 50 + 300 + 100
    assert r["busy_s"] == pytest.approx(450e-9)
    assert [(p["name"], p["program_id"], p["count"]) for p in r["programs"]] \
        == [("jit_step", 7, 2)]
    assert r["programs"][0]["device_s"] == pytest.approx(400e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-9)
    assert ops["copy.3"] == pytest.approx(50e-9)
    gaps = {(label, round(s * 1e9)) for label, s in r["idle_gaps"]}
    # [150,250] host in batch_wait; [550,950] midpoint 750 in ckpt_save;
    # [1050,1100] outside every span
    assert gaps == {("host: bench.batch_wait", 100), ("host: bench.ckpt_save", 400),
                    ("host: none", 50)}


def test_reduce_counts_nested_ops_by_self_time():
    host = "/host:CPU"
    events = [
        _ev("python", "bench.window", 0, 1000, plane=host),
        _ev("XLA Ops", "%while.1", 100, 600),
        _ev("XLA Ops", "%fusion.2", 150, 200),     # inside the while
        _ev("XLA Ops", "%copy.3", 400, 100),       # inside the while
        _ev("XLA Ops", "%fusion.4", 800, 100),
    ]
    r = bench_trace.reduce_events(events)
    ops = dict(r["device_ops"])
    assert ops["%while.1"] == pytest.approx(300e-9)
    assert ops["%fusion.2"] == pytest.approx(200e-9)
    assert ops["%copy.3"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(700e-9)


def test_reduce_needs_a_window_and_a_device():
    with pytest.raises(ValueError):
        bench_trace.reduce_events([_ev("XLA Ops", "x", 0, 1)])
    with pytest.raises(ValueError):
        bench_trace.reduce_events([_ev("python", "bench.window", 0, 10,
                                       plane="/host:CPU")])


def test_module_base():
    assert bench_trace.module_base("jit_train_step(123)") == ("jit_train_step", 123)
    assert bench_trace.module_base("jit__lambda") == ("jit__lambda", None)


RECORDED = sorted((HERE / "testdata").glob("*.trace.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_reduce_recorded_trace(path):
    rec = json.loads(path.read_text())
    r = bench_trace.reduce_events(rec["events"])
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    progs = {(p["name"], p["program_id"]): (p["count"], p["device_s"])
             for p in r["programs"]}
    for name, pid, count, dev_s in want["programs"]:
        assert progs[(name, pid)][0] == count
        assert progs[(name, pid)][1] == pytest.approx(dev_s, rel=1e-9)


# ---------------------------------------------------------------------------
# no chip: non-zero exit and no result line
# ---------------------------------------------------------------------------

def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
