"""The program's tracer (`repro.obs`) and the spans and counters placed in
the trainer, checkpoint manager, data pipeline, BuffetFS client and server
loop."""
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import BAgent, BLib, BuffetCluster
from repro.core.wire import MsgType, RpcStats


@pytest.fixture
def tracer():
    obs.drain()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.drain()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _self_ns(records, r):
    return (r.end_ns - r.start_ns) - sum(
        c.end_ns - c.start_ns for c in records if c.parent == r.id)


def test_nesting_parent_and_self_time(tracer):
    with obs.span("train.dispatch", step=3) as outer:
        with obs.span("fs.read_file") as a:
            pass
        with obs.span("fs.exists") as b:
            pass
    records, dropped = obs.drain()
    assert dropped == 0
    by = {r.id: r for r in records}
    assert by[a].parent == outer and by[b].parent == outer
    assert by[outer].parent is None and by[outer].attrs == {"step": 3}
    o = by[outer]
    assert o.start_ns <= by[a].start_ns <= by[a].end_ns <= by[b].start_ns \
        <= by[b].end_ns <= o.end_ns
    assert 0 <= _self_ns(records, o) == (o.end_ns - o.start_ns) - sum(
        by[i].end_ns - by[i].start_ns for i in (a, b))
    # children are recorded before their parent closes
    assert [r.id for r in records] == [a, b, outer]


def test_parent_stack_is_per_thread(tracer):
    got = {}

    def other():
        with obs.span("data.build_batch") as sid:
            got["id"] = sid

    with obs.span("train.batch_wait") as main:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    by = {r.id: r for r in obs.drain()[0]}
    assert by[got["id"]].parent is None and by[main].parent is None
    assert by[got["id"]].thread != by[main].thread


def test_off_records_nothing_and_never_annotates(monkeypatch):
    import jax

    entered = []

    class Recorder:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    obs.disable()
    obs.drain()
    with obs.span("ckpt.save", step=1) as sid:
        assert sid is None
    assert obs.span("a") is obs.span("b")          # one shared no-op
    assert obs.drain() == ([], 0) and entered == []
    obs.enable()
    try:
        with obs.span("ckpt.save", step=1):
            pass
    finally:
        obs.disable()
    records, _ = obs.drain()
    assert [r.name for r in records] == ["ckpt.save"]
    assert entered == ["ckpt.save"]


def test_record_cap_counts_dropped(tracer, monkeypatch):
    monkeypatch.setattr(obs, "MAX_RECORDS", 5)
    for i in range(8):
        with obs.span("fs.exists", i=i):
            pass
    records, dropped = obs.drain()
    assert [r.attrs["i"] for r in records] == [0, 1, 2, 3, 4]
    assert dropped == 3
    assert obs.drain() == ([], 0)


def test_rpcstats_wait_ns_counts_critical_only_and_resets():
    st = RpcStats()
    st.record(MsgType.READ, 10, 20, True, wait_ns=1500)
    st.record(MsgType.READ, 10, 20, True, wait_ns=500)
    st.record(MsgType.CLOSE, 10, 20, False, wait_ns=9999)
    assert st.snapshot()["wait_ns"] == {"READ": 2000}
    st.reset()
    assert st.snapshot()["wait_ns"] == {}


def test_transport_records_read_wait(tmp_path):
    c = BuffetCluster(root_dir=str(tmp_path), n_servers=2)
    a = BAgent(c)
    try:
        lib = BLib(a)
        lib.makedirs("/d")
        lib.write_file("/d/f", b"x" * 4096)
        a.stats.reset()
        assert lib.read_file("/d/f") == b"x" * 4096
        snap = a.stats.snapshot()
        assert snap["by_type"]["READ"] >= 1
        assert snap["wait_ns"]["READ"] > 0
        assert set(snap["wait_ns"]) <= set(snap["by_type"])
    finally:
        a.shutdown()
        c.shutdown()


def test_checkpoint_writer_names_its_cause(tmp_path, tracer):
    from repro.ckpt import CheckpointManager
    c = BuffetCluster(root_dir=str(tmp_path), n_servers=2)
    a = BAgent(c)
    try:
        ck = CheckpointManager(BLib(a), "t", parts=2, keep_last=1)
        tree = {"w": np.arange(8, dtype=np.float32), "b": np.ones(3)}
        ck.save(1, tree, block=False)
        ck.save(2, tree, block=False)
        ck.wait()
        step, got = ck.restore(like=tree)
        assert step == 2 and np.array_equal(got["w"], tree["w"])
    finally:
        a.shutdown()
        c.shutdown()
    records, _ = obs.drain()
    by_id = {r.id: r for r in records}
    names = _by_name(records)
    saves = {r.attrs["step"]: r for r in names["ckpt.save"]}
    writes = {r.attrs["step"]: r for r in names["ckpt.write"]}
    assert set(saves) == set(writes) == {1, 2}
    for s in (1, 2):
        w = writes[s]
        assert w.cause == saves[s].id and w.parent is None
        assert w.thread == "ckpt-writer" != saves[s].thread
    # the second save waited for the first write inside itself
    assert any(by_id[r.parent].name == "ckpt.save" for r in names["ckpt.wait"])
    for child in ("ckpt.serialize", "ckpt.crc", "ckpt.commit", "ckpt.gc"):
        assert {by_id[r.parent].name for r in names[child]} == {"ckpt.write"}
    assert {by_id[r.parent].name for r in names["ckpt.snapshot"]} == {"ckpt.save"}
    assert "ckpt.write" in {by_id[r.parent].name for r in names["fs.write_file"]
                            if r.parent is not None}
    restore = names["ckpt.restore"][0]
    assert restore.attrs == {"step": 2}
    for child in ("ckpt.verify", "ckpt.decode", "ckpt.assemble"):
        assert {r.parent for r in names[child]} == {restore.id}
    assert restore.id in {r.parent for r in names["fs.read_file"]}


TRAIN_SPANS = {"train.batch_wait", "train.h2d", "train.dispatch",
               "train.loss_sync", "train.fresh_state", "train.restore_put",
               "ckpt.save", "ckpt.wait", "ckpt.snapshot", "ckpt.write",
               "ckpt.serialize", "ckpt.crc", "ckpt.commit", "ckpt.gc",
               "ckpt.restore", "ckpt.verify", "ckpt.decode", "ckpt.assemble",
               "data.build_batch", "data.pack", "fs.read_file", "fs.write_file",
               "fs.makedirs", "fs.listdir", "fs.exists", "fs.unlink"}
# the spans that start a unit of work, and so carry its identifier
STEP_SPANS = {"train.batch_wait", "train.h2d", "train.dispatch",
              "train.loss_sync", "train.restore_put", "ckpt.save",
              "ckpt.write", "ckpt.restore", "data.build_batch"}


def test_trainer_run_and_restore_record_every_span(tmp_path, tracer):
    from repro.launch.train import Trainer, TrainerConfig
    tc = TrainerConfig(arch="mamba2-130m", steps=6, global_batch=2,
                       seq_len=32, ckpt_every=2, log_every=2,
                       data_dir=str(tmp_path), n_servers=2, run_name="ob")
    tr = Trainer(tc)
    tr.run()
    tr.shutdown()
    tr2 = Trainer(tc)
    tr2.init_or_restore()
    tr2.shutdown()
    assert tr2.start_step == 6
    records, dropped = obs.drain()
    assert dropped == 0
    names = _by_name(records)
    assert TRAIN_SPANS <= set(names), TRAIN_SPANS - set(names)
    for n in STEP_SPANS:
        assert all(isinstance(r.attrs.get("step"), int) for r in names[n]), n
    assert sorted(r.attrs["step"] for r in names["train.dispatch"]) \
        == list(range(6))
    assert sorted(r.attrs["step"] for r in names["ckpt.save"]) == [2, 4, 6]
    # the trainer's batches are the ones the producer built, step for step
    built = {r.attrs["step"] for r in names["data.build_batch"]}
    assert set(range(6)) <= built
    by_id = {r.id: r for r in records}
    for r in records:      # every span lies within its parent
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns


def test_server_generate_one_decode_step_per_token(tracer):
    from repro.launch.serve import Server
    srv = Server("stablelm-3b", reduced=True, max_len=32)
    prompts = np.random.default_rng(0).integers(
        1, srv.cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = srv.generate(prompts, 5)
    srv.generate(prompts, 3)
    assert out["tokens"].shape == (2, 5) and out["prefill_s"] > 0
    records, _ = obs.drain()
    names = _by_name(records)
    gens = sorted(names["serve.generate"], key=lambda r: r.attrs["batch"])
    assert [g.attrs["batch"] for g in gens] == [0, 1]
    for g, n in zip(gens, (5, 3)):
        steps = [r for r in names["serve.decode_step"] if r.parent == g.id]
        assert sorted(r.attrs["token"] for r in steps) == list(range(n))
        ids = {r.id for r in steps}
        for child in ("serve.token_sync", "serve.dispatch"):
            assert sum(r.parent in ids for r in names[child]) == n
        assert [r.parent for r in names["serve.prefill"]].count(g.id) == 1
        assert [r.parent for r in names["serve.init_cache"]].count(g.id) == 1


@pytest.mark.parametrize("arch,s0,share", [
    ("stablelm-3b", 8, 1.0),             # one query chunk: nothing skipped
    ("deepseek-v2-lite", 300, 0.75),     # two chunks of 150: 450 of 600 keys
])
def test_server_prefill_span_carries_attn_score_share(tracer, arch, s0, share):
    from repro.launch.serve import Server
    srv = Server(arch, reduced=True, max_len=s0 + 4)
    prompts = np.random.default_rng(2).integers(
        1, srv.cfg.vocab_size, size=(1, s0)).astype(np.int32)
    srv.generate(prompts, 1)
    records, _ = obs.drain()
    [prefill] = [r for r in records if r.name == "serve.prefill"]
    assert prefill.attrs == {"attn_score_share": share}


def test_server_reads_each_token_after_dispatching_its_step(tracer):
    """Within every decode step the host dispatches the next step before it
    waits for the token, and the tokens are the greedy ones that a loop
    which reads each token first produces.  An arch without a frontend stub
    never reads the token while dispatching."""
    import jax.numpy as jnp
    from repro.launch.serve import Server
    from repro.models import init_cache
    srv = Server("stablelm-3b", reduced=True, max_len=32)
    prompts = np.random.default_rng(1).integers(
        1, srv.cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = srv.generate(prompts, 6)
    records, _ = obs.drain()
    by_id = {r.id: r for r in records}
    for step in (r for r in records if r.name == "serve.decode_step"):
        kids = {by_id[i].name: by_id[i] for i in by_id
                if by_id[i].parent == step.id}
        assert (kids["serve.dispatch"].end_ns
                <= kids["serve.token_sync"].start_ns)

    logits, cache = srv._prefill(srv.params, init_cache(srv.cfg, 2, 32),
                                 {"tokens": jnp.asarray(prompts)})
    want = []
    for i in range(6):
        tok = np.asarray(jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
        want.append(tok)
        logits, cache = srv._decode(srv.params, cache,
                                    {"tokens": jnp.asarray(tok)[:, None]},
                                    jnp.int32(8 + i))
    np.testing.assert_array_equal(out["tokens"], np.stack(want, 1))

    class NotOnHost:             # a token array the host must not read
        def __array__(self, *a, **k):
            raise AssertionError("token brought to the host")
    assert srv._embed_stub(NotOnHost()) is None
