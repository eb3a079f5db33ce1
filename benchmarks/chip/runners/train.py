"""Runner for traffic of kind "train": a BuffetFS-fed training job that
resumes from its last checkpoint, trains, and saves asynchronously.

Set-up (counted in `setup_s`):
  1. The corpus is made from the seed and written through the program's
     `BuffetDataset.build` by a first `Trainer` (A) on an in-process
     `BuffetCluster` with the configuration's guarantees.
  2. A's own fresh-start `init_or_restore()` runs (it warms the eager path
     that the window's restore takes), then its state is replaced by the
     same init jitted from the seed.  A trains `prefix_steps` steps through
     its own `run()`, which commits a checkpoint.  Those steps compile the
     step program; their losses, the first gradient (from Adam's first
     moment after step 1) and the parameters' change are what the plain
     reference is held to.
Window:
  3. `restore_s`: a new `Trainer` (B) on the running cluster, through
     `init_or_restore()`, until its state is on the device.  B is handed
     A's compiled step, so nothing compiles in the window.
  4. `train_tokens_per_s`: B's `run()` over the remaining steps, with a save
     every `ckpt_every` steps and its final save-and-wait.  B logs its loss
     every `log_every` (= `ckpt_every`) steps, so the loop reads the loss of
     each save's step just before the save, and between saves it runs ahead
     of the device as the program does.  `ckpt_stall_s` is the time the
     loop spent inside `CheckpointManager.save`.
After the window: peak memory, then every check of `correct`.
"""
from __future__ import annotations

import gc
import math
import shutil
import statistics
import tempfile
import time
import zlib
from typing import Any, Dict, List

import numpy as np

import bench_harness as H
import bench_trace


# ---------------------------------------------------------------------------
# traffic: corpus and sample order, from the seed alone
# ---------------------------------------------------------------------------

def make_corpus(seed: int, tr: Dict[str, Any], vocab: int) -> np.ndarray:
    """[n_samples, sample_tokens] uint32 token ids in [1, vocab), Zipf(s)
    over ranks, drawn from the seed."""
    c = tr["corpus"]
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(c["zipf_s"]))
    cdf /= cdf[-1]
    u = rng.random(c["n_samples"] * c["sample_tokens"])
    ids = np.searchsorted(cdf, u, side="right") + 1
    return np.minimum(ids, vocab - 1).astype(np.uint32).reshape(
        c["n_samples"], c["sample_tokens"])


def sample_order(step: int, n_samples: int, batch: int, order_seed: int = 0
                 ) -> List[int]:
    """The sample indices of a global step: one permutation per epoch from
    (order_seed + epoch), read in batches (the data-parallel sampler's
    order for one rank)."""
    per_epoch = max(1, n_samples // batch)
    epoch, within = divmod(step, per_epoch)
    perm = np.random.default_rng(order_seed + epoch).permutation(n_samples)
    return [int(i) for i in perm[within * batch:(within + 1) * batch]]


def batch_arrays(corpus: np.ndarray, idx: List[int], seq: int
                 ) -> Dict[str, np.ndarray]:
    rows = corpus[idx, : seq + 1].astype(np.int32)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:],
            "loss_mask": np.ones((len(idx), seq), np.float32)}


def batch_digest(b: Dict[str, np.ndarray]) -> int:
    crc = 0
    for k in ("tokens", "labels", "loss_mask"):
        crc = zlib.crc32(np.ascontiguousarray(b[k]).tobytes(), crc)
    return crc


def window_plan(tr: Dict[str, Any], seconds: float) -> Dict[str, int]:
    """Fixed work for a window of `seconds`: m saves, every `ckpt_every`
    steps, the last one at the window's final step."""
    k = tr["ckpt_every"]
    m = max(1, round(seconds / (k * tr["nominal_step_s"])))
    end = m * k
    if end <= tr["prefix_steps"]:
        raise H.BenchError("window ends before the prefix steps")
    return {"saves": m, "end": end, "steps": end - tr["prefix_steps"]}


# ---------------------------------------------------------------------------
# thin proxies in benchmark code; the program is not edited
# ---------------------------------------------------------------------------

class BatchProxy:
    """Stands in for the Trainer's `pipeline`: times each wait for a batch
    and keeps a digest of every batch handed to the step, and the shapes
    of the first."""

    def __init__(self, inner, spans: H.Spans, span: str, digests: List[int]):
        self._inner, self._spans, self._span = inner, spans, span
        self.digests = digests
        self.shapes = None

    def __iter__(self):
        it = iter(self._inner)
        while True:
            with self._spans.span(self._span):
                try:
                    b = next(it)
                except StopIteration:
                    return
            self.digests.append(batch_digest(b))
            if self.shapes is None:
                self.shapes = H.abstract(b)
            yield b

    def __getattr__(self, name):
        return getattr(self._inner, name)


# ---------------------------------------------------------------------------
# device-side readings of the program's state
# ---------------------------------------------------------------------------

def _jits():
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)

    def change(a, b):
        return norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))

    def fingerprint(tree):
        def one(x):
            bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
            u = jax.lax.bitcast_convert_type(x, bits).astype(jnp.uint32).ravel()
            w = jnp.arange(u.size, dtype=jnp.uint32) * jnp.uint32(2654435761) \
                + jnp.uint32(1)
            return jnp.sum(u * w, dtype=jnp.uint32)
        return jax.tree_util.tree_map(one, tree)

    return jax.jit(norms), jax.jit(change), jax.jit(fingerprint)


def _by_path(tree) -> Dict[str, Any]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): v for kp, v in flat}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: List[str]) -> float:
    """max over leaves of |prog - ref| / max(ref leaf, median ref leaf)."""
    med = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= 1e-3 * med]


def readings_against(ref: Dict[str, Any], losses: List[float],
                     grad: Dict[str, float], change: Dict[str, float]
                     ) -> Dict[str, float]:
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(losses, ref["losses"]))
    leaves = sorted(ref["grad_norms"])
    return {
        "loss_rel_gap": loss_gap,
        "grad_norm_gap": worst_leaf_gap(grad, ref["grad_norms"], leaves),
        "update_norm_gap": worst_leaf_gap(change, ref["change_norms"],
                                          moved_leaves(ref["grad_norms"])),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def trainer_config(a: H.RunArgs, work: str):
    from repro.launch.train import TrainerConfig
    tr, prog = a.traffic, a.config["program"]
    return TrainerConfig(arch=prog["arch"], reduced=bool(prog.get("reduced")),
                         steps=tr["schedule_steps"],
                         global_batch=tr["global_batch"], seq_len=tr["seq_len"],
                         lr=tr["lr"], ckpt_every=tr["ckpt_every"],
                         run_name=tr["run_name"],
                         n_servers=a.config["guarantees"]["n_servers"],
                         data_dir=work)


def check_optimizer(opt_cfg, want: Dict[str, Any]) -> None:
    for k, v in want.items():
        got = getattr(opt_cfg, k)
        if float(got) != float(v):
            raise H.BenchError(f"program's optimizer {k} is {got!r}, the traffic "
                               f"file states {v!r}")


def setup_prefix(a: H.RunArgs, cluster, corpus, work: str) -> Dict[str, Any]:
    """Trainer A: builds the corpus, drives the step through the prefix
    steps from the seed, and commits the checkpoint the window resumes."""
    import jax

    from repro.launch.train import Trainer
    from repro.runtime.steps import make_train_state

    tr = a.traffic
    norms, change, fingerprint = _jits()
    A = Trainer(trainer_config(a, work), cluster=cluster, corpus=list(corpus))
    if A.cfg != H.program_config(a.config):
        raise H.BenchError("Trainer's model config differs from the file's")
    check_optimizer(A.opt_cfg, tr["optimizer"])
    A.init_or_restore()                   # fresh start: the program's eager init
    if A.start_step != 0:
        raise H.BenchError("set-up found a checkpoint before it made one")
    state0 = jax.jit(lambda k: make_train_state(A.cfg, A.opt_cfg, k))(
        H.key_from_seed(a.seed))
    params0 = jax.tree_util.tree_map(lambda x: x.copy(), state0["params"])
    A.state = state0
    if a.plant is not None:
        a.plant(A)
    step_fn = A.step_fn
    b1 = A.opt_cfg.b1
    first: Dict[str, Any] = {}

    def first_steps(state, batch):
        new, metrics = step_fn(state, batch)
        if not first:   # the gradient as Adam got it: m_1 / (1 - b1)
            first["grad"] = norms(jax.tree_util.tree_map(
                lambda m: m / (1.0 - b1), new["opt"]["m"]))
        return new, metrics

    digests: List[int] = []
    A.step_fn = first_steps
    A.pipeline = proxy = BatchProxy(A.pipeline, a.spans, "bench.setup_batch",
                                    digests)
    A.tc.steps = tr["prefix_steps"]
    A.tc.log_every = 1
    out = A.run()
    losses = [out["losses"][k] for k in range(1, tr["prefix_steps"] + 1)]
    got = jax.device_get({
        "grad": first["grad"],
        "change": change(A.state["params"], params0),
        "saved_fp": fingerprint(A.state),
    })
    A.step_fn = step_fn
    A.state = None
    A.agent.shutdown()
    del state0, params0
    gc.collect()
    return {"step_fn": step_fn, "losses": losses,
            "grad": _by_path(got["grad"]),
            "change": _by_path(got["change"]), "saved_fp": got["saved_fp"],
            "digests": digests, "fingerprint": fingerprint,
            "batch_shapes": proxy.shapes}


def run(a: H.RunArgs) -> H.RunResult:
    import jax

    from repro.core import BuffetCluster
    from repro.launch.train import Trainer

    tr, g = a.traffic, a.config["guarantees"]
    cfg = H.program_config(a.config)
    plan = window_plan(tr, a.seconds)
    corpus = make_corpus(a.seed, tr, cfg.vocab_size)
    work = tempfile.mkdtemp(prefix="chipbench_train_")
    cluster = BuffetCluster(root_dir=work, n_servers=g["n_servers"],
                            replicas=g["replicas"],
                            stripe_count=g["stripe_count"],
                            fsync_policy=g["fsync_policy"])
    compiles = H.CompileCounter()
    try:
        pre = setup_prefix(a, cluster, corpus, work)
        B_tokens = tr["global_batch"] * tr["seq_len"]
        digests: List[int] = []
        trace_dir = H.TRACE_DIR / a.workload
        if a.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            bench_trace.start(trace_dir)
        setup_s = time.perf_counter() - a.t_start
        c0 = compiles.count

        # ---- window ----
        t0 = time.perf_counter()
        with a.spans.span("bench.restore"):
            B = Trainer(trainer_config(a, work), cluster=cluster)
            B.ckpt.restore = a.spans.wrap(B.ckpt.restore, "bench.ckpt_restore")
            B.step_fn = pre["step_fn"]       # A's compiled step, the same object
            B.init_or_restore()
            jax.block_until_ready(B.state)
        restore_s = time.perf_counter() - t0
        restored_fp = pre["fingerprint"](B.state)
        resumed_at = B.start_step
        B.step_fn = a.spans.wrap(pre["step_fn"], "bench.step_call")
        B.ckpt.save = a.spans.wrap(B.ckpt.save, "bench.ckpt_save")
        B.ckpt.wait = a.spans.wrap(B.ckpt.wait, "bench.ckpt_wait")
        B.pipeline = BatchProxy(B.pipeline, a.spans, "bench.batch_wait", digests)
        B.tc.steps = plan["end"]
        B.tc.log_every = tr["log_every"]
        rpc0 = dict(B.agent.stats.snapshot()["by_type"])
        s0 = B.pipeline.stats.samples
        t1 = time.perf_counter()
        with a.spans.span("bench.window"):
            out = B.run()
        t2 = time.perf_counter()
        rpc1 = dict(B.agent.stats.snapshot()["by_type"])
        s1 = B.pipeline.stats.samples
        if a.trace:
            bench_trace.stop()
        in_window = compiles.count - c0
        device = H.device_info(a.devices, {"train_step": H.footprint(
            pre["step_fn"], H.abstract(B.state), pre["batch_shapes"])})
        H.log(f"compiles after the window, for the footprint: "
              f"{compiles.count - c0 - in_window}")

        steps = plan["end"] - resumed_at
        metrics = {
            "train_tokens_per_s": steps * B_tokens / (t2 - t1),
            "ckpt_stall_s": a.spans.total("bench.ckpt_save"),
            "restore_s": restore_s,
            "setup_s": setup_s,
        }
        waits = a.spans.durations("bench.batch_wait")
        ctx: Dict[str, Any] = {
            "steps": steps, "window_s": t2 - t1, "compiles_in_window": in_window,
            "data_wait_ms": 1e3 * sum(waits) / max(len(waits), 1),
            "ckpt_restore_s": a.spans.total("bench.ckpt_restore"),
            "reads": rpc1.get("READ", 0) - rpc0.get("READ", 0),
            "samples": s1 - s0,
            "config": a.config, "traffic": tr,
        }

        t_check = time.perf_counter()
        # ---- checks: the window's own outputs ----
        mismatched = sum(int(x != y) for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(restored_fp)),
            jax.tree_util.tree_leaves(pre["saved_fp"])))
        final = B.ckpt.latest_step()
        _, saved = B.ckpt.restore(like=B.state)
        held = jax.device_get(B.state)
        ckpt_bad = sum(int(not np.array_equal(np.asarray(x), np.asarray(y)))
                       for x, y in zip(jax.tree_util.tree_leaves(saved),
                                       jax.tree_util.tree_leaves(held)))
        ckpt_bad += int(final != plan["end"])
        del saved, held
        n_all = tr["corpus"]["n_samples"]
        want = [batch_digest(batch_arrays(
                    corpus, sample_order(s, n_all, tr["global_batch"]),
                    tr["seq_len"]))
                for s in range(resumed_at + steps)]
        got = pre["digests"] + digests
        batch_bad = sum(int(x != y) for x, y in zip(got, want)) \
            + abs(len(got) - len(want))
        B.state = None
        B.pipeline.stop()
        B.agent.shutdown()
        gc.collect()

        # ---- checks: the plain reference over the prefix steps ----
        batches = [batch_arrays(corpus, sample_order(s, n_all, tr["global_batch"]),
                                tr["seq_len"])
                   for s in range(tr["prefix_steps"])]
        ref = a.reference.train_readings(a.config, tr, H.key_from_seed(a.seed),
                                         batches)
        readings = readings_against(ref, pre["losses"], pre["grad"],
                                    pre["change"])
        readings.update({"restore_mismatched_leaves": float(mismatched),
                         "ckpt_mismatched_leaves": float(ckpt_bad),
                         "batch_mismatches": float(batch_bad)})
        if a.control:
            # the reference in the program's place, at fp8 and with half of
            # each batch left out; the run's exact readings stand for both
            key = H.key_from_seed(a.seed)
            for name, kw in (("control", {"precision": "fp8"}),
                             ("half_batch", {"drop_half": True})):
                alt = a.reference.train_readings(a.config, tr, key, batches, **kw)
                ctx[name] = H.control_verdict(dict(readings, **readings_against(
                    ref, alt["losses"], alt["grad_norms"], alt["change_norms"])),
                    a.limits)
        ctx["readings"] = readings
        ctx["check_s"] = time.perf_counter() - t_check
        failed = sum(int(not math.isfinite(v)) for v in
                     list(out["losses"].values()) + pre["losses"])
        result = H.RunResult(metrics=metrics,
                             checks=H.checks_from(readings, a.limits),
                             attempted=steps, failed=failed, device=device,
                             ctx=ctx)
        if a.trace:
            reduced = bench_trace.reduce_events(bench_trace.load_events(
                bench_trace.newest_xplane(trace_dir)))
            ctx["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result.breakdown = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
        return result
    finally:
        try:
            cluster.shutdown()
        finally:
            shutil.rmtree(work, ignore_errors=True)
