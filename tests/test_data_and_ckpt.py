"""Data pipeline + checkpoint layer tests over a live BuffetFS cluster."""
import time

import numpy as np
import pytest

from repro.core import BAgent, BLib, BuffetCluster
from repro.core.failure import slow_server
from repro.data import (BuffetDataset, DataPipeline, ShardedSampler,
                        decode_sample, encode_sample, pack_batch)
from repro.ckpt import CheckpointManager


@pytest.fixture()
def cluster(tmp_path):
    c = BuffetCluster(root_dir=str(tmp_path), n_servers=4)
    yield c
    c.shutdown()


@pytest.fixture()
def lib(cluster):
    agent = BAgent(cluster)
    yield BLib(agent)
    agent.shutdown()


def _mk_corpus(lib, n=64, seq=32, replicate=False, name="c0"):
    rng = np.random.default_rng(0)
    samples = [rng.integers(1, 1000, size=seq).astype(np.uint16) for _ in range(n)]
    return BuffetDataset.build(lib, samples, name=name, shard_size=16,
                               replicate=replicate), samples


def test_sample_codec_roundtrip():
    s = np.arange(100, dtype=np.uint32)
    assert np.array_equal(decode_sample(encode_sample(s)), s)
    s16 = np.arange(50, dtype=np.uint16)
    assert np.array_equal(decode_sample(encode_sample(s16)), s16)


def test_pack_batch_shapes():
    toks, mask = pack_batch([np.arange(5), np.arange(9)], seq_len=8)
    assert toks.shape == (2, 8) and mask.shape == (2, 8)
    assert mask[0].sum() == 5 and mask[1].sum() == 8


def test_dataset_roundtrip(lib):
    ds, samples = _mk_corpus(lib)
    assert len(ds) == 64
    for i in (0, 15, 16, 63):
        assert np.array_equal(ds.read_sample(i), samples[i])


def test_sampler_disjoint_and_resumable():
    s0 = ShardedSampler(n_samples=128, global_batch=16, dp_rank=0, dp_size=4)
    s1 = ShardedSampler(n_samples=128, global_batch=16, dp_rank=1, dp_size=4)
    a, b = s0.indices_for_step(3), s1.indices_for_step(3)
    assert not set(a) & set(b)
    assert len(a) == len(b) == 4
    # resumable: same step -> same indices
    s0.step = 7
    st = s0.state_dict()
    s2 = ShardedSampler(n_samples=128, global_batch=16, dp_rank=0, dp_size=4)
    s2.load_state_dict(st)
    assert s2.indices_for_step(s2.step) == s0.indices_for_step(s0.step)


def test_pipeline_produces_batches(cluster, lib):
    ds, _ = _mk_corpus(lib)
    sampler = ShardedSampler(n_samples=len(ds), global_batch=8, dp_rank=0, dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16, prefetch=2)
    it = iter(pipe)
    for _ in range(4):
        batch = next(it)
        assert batch["tokens"].shape == (8, 16)
        assert batch["labels"].shape == (8, 16)
        assert not np.isnan(batch["loss_mask"]).any()
    pipe.stop()


def test_pipeline_state_counts_consumed_batches(cluster, lib):
    """The producer runs ahead of the consumer; the resumable state is the
    count of batches the consumer took, not the sampler's cursor."""
    ds, _ = _mk_corpus(lib)
    sampler = ShardedSampler(n_samples=len(ds), global_batch=4, dp_rank=0,
                             dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16, prefetch=2)
    it = iter(pipe)
    for _ in range(3):
        next(it)
    deadline = time.time() + 10
    while sampler.step <= 3 and time.time() < deadline:
        time.sleep(0.01)
    assert sampler.step > 3  # prefetch ran ahead
    assert pipe.state_dict() == {"step": 3, "seed": 0}
    pipe.stop()


def test_pipeline_epoch_rpc_efficiency(cluster):
    """After warm-up, one epoch over N samples costs ~N critical RPCs —
    the BuffetFS property, measured end-to-end through the pipeline."""
    agent = BAgent(cluster)
    lib = BLib(agent)
    ds, _ = _mk_corpus(lib, n=32)
    sampler = ShardedSampler(n_samples=32, global_batch=8, dp_rank=0, dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16, prefetch=1, io_threads=2)
    pipe.dataset.warm_dirs()
    agent.drain()
    time.sleep(0.05)
    agent.stats.reset()
    it = iter(pipe)
    for _ in range(4):  # one epoch = 32 samples
        next(it)
    pipe.stop()
    snap = agent.stats.snapshot()
    # prefetch may have read at most one extra batch ahead
    assert snap["by_type"]["READ"] <= 32 + 8
    assert snap["by_type"].get("LOOKUP_DIR", 0) <= 2, snap  # nothing re-fetched
    agent.shutdown()


def test_hedged_read_beats_straggler(cluster):
    agent = BAgent(cluster)
    lib = BLib(agent)
    ds, samples = _mk_corpus(lib, n=32, replicate=True, name="hedged")
    sampler = ShardedSampler(n_samples=32, global_batch=4, dp_rank=0, dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16, hedge_delay_s=0.02, io_threads=4)
    # find which host serves shard_0000 and make it a straggler
    from repro.core.inode import Inode
    shard_host = Inode.unpack(agent.stat_cached(f"{ds.base}/shard_0000")["ino"]).host_id
    with slow_server(cluster, shard_host, extra_delay_s=0.2):
        it = iter(pipe)
        batch = next(it)
    pipe.stop()
    assert batch["tokens"].shape == (4, 16)
    assert pipe.stats.hedged >= 1  # hedging actually fired
    agent.shutdown()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree():
    return {
        "w": np.arange(64, dtype=np.float32).reshape(8, 8),
        "b": np.ones((8,), dtype=np.float32),
        "inner": {"scale": np.float32(2.5) * np.ones((4, 2))},
    }


def test_ckpt_save_restore_roundtrip(lib):
    mgr = CheckpointManager(lib, "runA", parts=4, keep_last=10)
    tree = _tree()
    mgr.save(10, tree, extra={"lr": 0.1})
    step, restored = mgr.restore(like=_tree())
    assert step == 10
    np.testing.assert_array_equal(restored["w"], tree["w"])
    np.testing.assert_array_equal(restored["inner"]["scale"], tree["inner"]["scale"])
    assert mgr.manifest(10).extra["lr"] == 0.1


def test_ckpt_async_save(lib):
    mgr = CheckpointManager(lib, "runB", parts=2)
    tree = _tree()
    mgr.save(1, tree, block=False)
    mgr.wait()
    step, restored = mgr.restore(like=_tree())
    assert step == 1
    np.testing.assert_array_equal(restored["b"], tree["b"])


def test_ckpt_latest_and_gc(lib):
    mgr = CheckpointManager(lib, "runC", parts=2, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.latest_step() == 4
    assert mgr.steps() == [3, 4]  # older steps GC'd


def test_ckpt_uncommitted_invisible(lib):
    mgr = CheckpointManager(lib, "runD", parts=2)
    mgr.save(5, _tree())
    # simulate a torn save: step dir exists but no MANIFEST
    sdir = mgr._step_dir(9)
    lib.makedirs(f"{sdir}/part_000")
    lib.write_file(f"{sdir}/part_000/w.npy", b"garbage")
    assert mgr.latest_step() == 5


def test_ckpt_elastic_parts(lib):
    """Save with 4 parts, restore through a manager configured differently —
    restore is driven by the manifest, not the current config."""
    m4 = CheckpointManager(lib, "runE", parts=4)
    tree = _tree()
    m4.save(7, tree)
    m1 = CheckpointManager(lib, "runE", parts=1)
    step, restored = m1.restore(like=_tree())
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_ckpt_corruption_detected(lib):
    mgr = CheckpointManager(lib, "runF", parts=1)
    mgr.save(3, _tree())
    man = mgr.manifest(3)
    victim = man.leaves[0]["files"][0]["path"]
    lib.write_file(victim, b"corrupted bytes")
    with pytest.raises(IOError):
        mgr.restore(3, like=_tree())


def test_ckpt_restore_like_shapes(lib):
    """`like` may be shapes only: restore reads each leaf's shape and dtype
    and never needs its values."""
    import jax
    mgr = CheckpointManager(lib, "runG", parts=2)
    tree = _tree()
    mgr.save(2, tree)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, np.float16), tree)
    step, restored = mgr.restore(like=like)
    assert step == 2
    assert restored["w"].dtype == np.float16
    np.testing.assert_array_equal(restored["w"], tree["w"].astype(np.float16))
    bad = dict(like, b=jax.ShapeDtypeStruct((9,), np.float32))
    with pytest.raises(ValueError, match="ckpt shape"):
        mgr.restore(like=bad)


def test_hedged_read_survives_dead_server(cluster):
    """A DEAD primary BServer (not just slow) must fail over to the replica:
    the primary future raises immediately, which must trigger the hedge
    rather than killing the pipeline producer."""
    from repro.core.failure import server_down
    from repro.core.inode import Inode
    agent = BAgent(cluster)
    lib = BLib(agent)
    ds, samples = _mk_corpus(lib, n=32, replicate=True, name="deadsrv")
    shard_host = Inode.unpack(
        agent.stat_cached(f"{ds.base}/shard_0000")["ino"]).host_id
    sampler = ShardedSampler(n_samples=32, global_batch=4, dp_rank=0, dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16, hedge_delay_s=0.05)
    with server_down(cluster, shard_host):
        batch = next(iter(pipe))
    pipe.stop()
    assert batch["tokens"].shape == (4, 16)
    assert pipe.stats.hedge_wins >= 1
    agent.shutdown()


def test_pipeline_surfaces_producer_errors(cluster):
    """If every copy of a sample is unreadable the iterator raises instead
    of hanging forever."""
    agent = BAgent(cluster)
    lib = BLib(agent)
    ds, _ = _mk_corpus(lib, n=8, name="err")
    # corrupt the index so sample paths point at nothing
    ds._spec = None
    lib.write_file(f"{ds.base}/INDEX",
                   b'{"name":"err","n_shards":1,"samples_per_shard":[8],'
                   b'"seq_len_hint":0,"replicated":false}')
    lib.unlink(f"{ds.base}/shard_0000/s_000003.tok")
    sampler = ShardedSampler(n_samples=8, global_batch=8, dp_rank=0, dp_size=1)
    pipe = DataPipeline(ds, sampler, seq_len=16)
    with pytest.raises(Exception):
        next(iter(pipe))
    pipe.stop()
    agent.shutdown()
