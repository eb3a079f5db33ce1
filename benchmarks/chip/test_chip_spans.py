"""The program's spans in the chip benchmark, on the CPU: each reader of
`bench_spans` gives a finite value from a small run of its cell with the
tracer on, the program's spans agree with the benchmark's outside spans on
the same run, and device idle time is named by program spans on a
hand-made trace."""
import json
import math
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_small  # noqa: E402
import bench_spans  # noqa: E402

TRAIN = "mamba2-130m.train-ckpt"
SERVE = "stablelm-3b.decode"
SEED = 3_000_000_023
TRAIN_METRICS = ["ckpt_snapshot_s", "ckpt_write_s", "ckpt_write_fs_share",
                 "ckpt_restore_fs_share", "fs_read_rpc_us"]


def _traced_small(workload):
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, workload)
    traffic = bench_small.small_traffic(cell["traffic"])
    args = H.RunArgs(
        workload=workload, seed=SEED, seconds=1.0, trace=False,
        config=bench_small.small_config(cell["config"]), traffic=traffic,
        limits=H.load_checks(workload),
        reference=H.load_reference(spec, cell["config"]),
        devices=[bench_small.FakeDevice()], t_start=time.perf_counter())
    result, records, dropped = bench_spans.run_traced(
        H.load_runner(traffic["kind"]), args)
    return args, result, records, dropped


@pytest.fixture(scope="module")
def train_traced():
    return _traced_small(TRAIN)


@pytest.fixture(scope="module")
def serve_traced():
    return _traced_small(SERVE)


def _total(records, name, window):
    return sum(r.end_ns - r.start_ns for r in records if r.name == name
               and window[0] <= r.start_ns / 1e9 <= window[1]) / 1e9


@pytest.mark.parametrize("metric", TRAIN_METRICS)
def test_train_reader_reads_a_finite_value(train_traced, metric):
    _, result, _, dropped = train_traced
    assert result.correct and dropped == 0
    v = H.load_module(HERE / "layer_metrics" / f"{metric}.py").read(
        result.ctx, result.device)
    assert v is not None and math.isfinite(v) and v > 0
    if metric.endswith("_share"):
        assert v <= 100.0


def test_decode_reader_reads_a_finite_value(serve_traced):
    _, result, records, _ = serve_traced
    assert result.correct
    v = H.load_module(HERE / "layer_metrics" / "decode_host_ms.py").read(
        result.ctx, result.device)
    assert v is not None and math.isfinite(v) and v > 0
    assert "fs_read_rpc_us" not in result.ctx


def test_program_spans_agree_with_outside_spans(train_traced):
    args, result, records, _ = train_traced
    win = bench_spans.window_of(args.spans.records, "bench.window")
    rwin = bench_spans.window_of(args.spans.records, "bench.restore")
    waits = [r for r in records if r.name == "train.batch_wait"
             and win[0] <= r.start_ns / 1e9 <= win[1]]
    assert len(waits) == result.ctx["steps"]
    mean_ms = 1e3 * _total(records, "train.batch_wait", win) / len(waits)
    assert mean_ms == pytest.approx(result.ctx["data_wait_ms"], abs=1.0)
    assert _total(records, "ckpt.restore", rwin) == pytest.approx(
        result.ctx["ckpt_restore_s"], rel=0.05)
    assert _total(records, "ckpt.save", win) == pytest.approx(
        result.metrics["ckpt_stall_s"], rel=0.05)


def test_writer_spans_name_their_cause_and_step(train_traced):
    args, _, records, _ = train_traced
    win = bench_spans.window_of(args.spans.records, "bench.window")
    by_id = {r.id: r for r in records}
    writes = [r for r in records if r.name == "ckpt.write"
              and win[0] <= r.start_ns / 1e9 <= win[1]]
    assert writes
    for w in writes:
        save = by_id[w.cause]
        assert save.name == "ckpt.save" and save.attrs["step"] == w.attrs["step"]
        assert w.thread != save.thread


def test_slowest_decode_step_names_its_children(serve_traced):
    args, _, records, _ = serve_traced
    win = bench_spans.window_of(args.spans.records, "bench.window")
    s = bench_spans.slowest_decode_step(records, win)
    assert s["ms"] >= s["median_ms"] > 0
    assert {n for n, _, _ in s["spans"]} >= {"serve.token_sync", "serve.dispatch"}


# ---------------------------------------------------------------------------
# idle gaps named by program spans
# ---------------------------------------------------------------------------

def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_idle_by_program_span_hand_made_trace():
    host = "/host:CPU"
    events = [
        _ev("python", "bench.window", 0, 1000, plane=host),
        _ev("XLA Ops", "fusion.1", 0, 100),
        _ev("XLA Ops", "fusion.2", 900, 50),
        # main thread: a wait holding [100, 900], a token sync inside it
        _ev("main", "ckpt.wait", 100, 800, plane=host),
        _ev("main", "serve.token_sync", 150, 50, plane=host),
        # writer thread: serialize then write, inside the wait
        _ev("writer", "ckpt.write", 120, 700, plane=host),
        _ev("writer", "ckpt.serialize", 300, 100, plane=host),
        _ev("writer", "fs.write_file", 400, 300, plane=host),
        # ignored: a benchmark span, and a span outside the window
        _ev("main", "bench.ckpt_wait", 100, 800, plane=host),
        _ev("main", "train.dispatch", 1100, 10, plane=host),
    ]
    r = bench_spans.idle_by_program_span(events)
    got = {k: round(v * 1e9) for k, v, _ in r["by_span"]}
    # idle: [100, 900] and [950, 1000]; ckpt.write (700) is shorter than
    # ckpt.wait (800), so it names [120, 820] where nothing shorter is open
    assert got == {"ckpt.wait": 20 + 80, "serve.token_sync": 50,
                   "ckpt.write": 30 + 100 + 120, "ckpt.serialize": 100,
                   "fs.write_file": 300, "none": 50}
    assert r["idle_s"] == pytest.approx(850e-9)
    assert sum(s for _, _, s in r["by_span"]) == pytest.approx(100.0)


def test_idle_by_program_span_needs_window_and_device():
    with pytest.raises(ValueError):
        bench_spans.idle_by_program_span([_ev("XLA Ops", "x", 0, 1)])
    with pytest.raises(ValueError):
        bench_spans.idle_by_program_span(
            [_ev("python", "bench.window", 0, 10, plane="/host:CPU")])


def test_save_slice_keeps_the_last_save_and_reduces(tmp_path):
    host = "/host:CPU"
    events = [
        _ev("python", "bench.window", 0, 10_000_000, plane=host),
        _ev("XLA Modules", "jit_step(3)", 0, 3_000_000),
        _ev("XLA Ops", "fusion.1", 0, 3_000_000),
        _ev("main", "ckpt.save", 4_000_000, 1_000_000, plane=host),
        _ev("main", "ckpt.snapshot", 4_100_000, 800_000, plane=host),
        _ev("main", "ckpt.wait", 5_000_000, 5_000_000, plane=host),
        _ev("writer", "ckpt.write", 5_000_000, 4_000_000, plane=host),
        _ev("writer", "ckpt.serialize", 5_000_000, 1_000_000, plane=host),
        _ev("writer", "fs.write_file", 6_000_000, 2_000_000, plane=host),
        _ev("writer", "ckpt.crc", 8_000_000, 500_000, plane=host),
    ]
    path = tmp_path / "x.spans.trace.json"
    rec = bench_spans.save_slice(events, path)
    assert json.loads(path.read_text()) == rec
    win = [e for e in rec["events"] if e["name"] == "bench.window"]
    assert [(e["start_ns"], e["dur_ns"]) for e in win] == [(0.0, 10_000_000.0)]
    got = {k: v for k, v, _ in rec["expected"]["idle_by_program_span"]["by_span"]}
    # idle [3, 10] ms: nothing open until the save at 4 ms
    assert got == pytest.approx({
        "none": 0.001, "ckpt.save": 0.0002, "ckpt.snapshot": 0.0008,
        "ckpt.serialize": 0.001, "fs.write_file": 0.002, "ckpt.crc": 0.0005,
        "ckpt.write": 0.0005, "ckpt.wait": 0.001})
    assert rec["expected"]["busy_s"] == pytest.approx(0.003)
