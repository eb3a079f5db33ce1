"""Runner for traffic of kind "serve": static batches through the program's
`Server.generate`, in a closed loop.

Traffic: each request has a prompt length and an answer length, lognormal
with the mean and spread that the traffic file takes from its source, the
answer capped at `max_new_tokens`.  The window's requests are the quantiles
of those distributions, paired and grouped into batches of `batch` by a
fixed generator, so every seed serves the same set of batches; the seed
orders the batches and the rows, and draws the prompt tokens.  A batch
decodes until its longest answer, and each request keeps only its own
answer's tokens: the rest is the waste of static batching.  A batch's
prompts all have the length of the smallest of `prompt_buckets` that holds
its longest drawn prompt, since `Server.generate` takes one prompt length a
batch and no padding mask.

Set-up (counted in `setup_s`): the weights are made on the device from the
seed by the program's own init in one jitted call and handed to `Server`;
one short request per prompt length of the window compiles prefill at that
length and the decode step.
Window: `serve_tokens_per_s` is the requests' own answer tokens over the
window's wall time.
After the window: peak memory, the program's state is freed, then the plain
reference reads a seeded sample of the served answers, the longest request
among them.
"""
from __future__ import annotations

import gc
import math
import shutil
import statistics
import time
from typing import Any, Dict, List, Tuple

import numpy as np

import bench_harness as H
import bench_trace


def lognormal_quantiles(dist: Dict[str, float], n: int) -> List[float]:
    """The n quantiles (i + 1/2) / n of the lognormal with `dist`'s mean and
    standard deviation."""
    s2 = math.log(1.0 + (dist["sd"] / dist["mean"]) ** 2)
    mu, sigma = math.log(dist["mean"]) - s2 / 2, math.sqrt(s2)
    nd = statistics.NormalDist()
    return [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]


def request_deck(tr: Dict[str, Any], seconds: float) -> List[Dict[str, Any]]:
    """The window's batches, the same for every seed: each a prompt length
    and the answer length of each row."""
    b = tr["batch"]
    n_batches = max(1, round(seconds / tr["nominal_batch_s"]))
    n = n_batches * b
    prompts = lognormal_quantiles(tr["prompt_tokens"], n)
    answers = [min(max(1, round(x)), tr["max_new_tokens"])
               for x in lognormal_quantiles(tr["answer_tokens"], n)]
    rng = np.random.default_rng(0)     # the same pairing for every seed
    p_order, a_order = rng.permutation(n), rng.permutation(n)
    buckets = tr["prompt_buckets"]
    deck = []
    for j in range(n_batches):
        rows = range(j * b, (j + 1) * b)
        longest = max(prompts[p_order[k]] for k in rows)
        deck.append({"prompt": next((x for x in buckets if x >= longest),
                                    buckets[-1]),
                     "answers": [answers[a_order[k]] for k in rows]})
    return deck


def seeded_order(seed: int, deck: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The deck's batches, and the rows within each, in the seed's order."""
    rng = np.random.default_rng(seed)
    return [{"prompt": deck[j]["prompt"],
             "answers": [deck[j]["answers"][r]
                         for r in rng.permutation(len(deck[j]["answers"]))]}
            for j in rng.permutation(len(deck))]


def prompts_for(seed: int, deck: List[Dict[str, Any]], vocab: int
                ) -> List[np.ndarray]:
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(1, vocab, size=(len(q["answers"]), q["prompt"])
                         ).astype(np.int32) for q in deck]


def check_sample(seed: int, deck: List[Dict[str, Any]], min_tokens: int
                 ) -> List[Tuple[int, int]]:
    """(batch, row) pairs whose answers the reference reads: the longest
    request first, then others drawn from the seed, until at least
    `min_tokens` served tokens are covered."""
    rng = np.random.default_rng([seed, 2])
    reqs = [(j, r) for j, q in enumerate(deck) for r in range(len(q["answers"]))]

    def length(jr):
        return deck[jr[0]]["prompt"] + deck[jr[0]]["answers"][jr[1]]
    longest = max(reqs, key=lambda jr: (length(jr), -jr[0], -jr[1]))
    picks = [longest]
    total = deck[longest[0]]["answers"][longest[1]]
    for i in rng.permutation(len(reqs)):
        if total >= min_tokens:
            break
        jr = reqs[int(i)]
        if jr != longest:
            picks.append(jr)
            total += deck[jr[0]]["answers"][jr[1]]
    return picks


def run(a: H.RunArgs) -> H.RunResult:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import Server
    from repro.models import init_cache, init_model

    tr, prog = a.traffic, a.config["program"]
    cfg = H.program_config(a.config)
    b = tr["batch"]
    deck = seeded_order(a.seed, request_deck(tr, a.seconds))
    prompts = prompts_for(a.seed, deck, cfg.vocab_size)

    params = jax.jit(lambda k: init_model(cfg, k)[0])(H.key_from_seed(a.seed))
    srv = Server(prog["arch"], reduced=bool(prog.get("reduced")),
                 max_len=tr["max_len"], params=params)
    del params
    if srv.cfg != cfg:
        raise H.BenchError("Server's model config differs from the file's")
    if a.plant is not None:
        a.plant(srv)
    prefill, decode = srv._prefill, srv._decode
    srv._prefill = a.spans.wrap(prefill, "bench.prefill_call")
    srv._decode = a.spans.wrap(decode, "bench.decode_call")
    compiles = H.CompileCounter()
    warm_rng = np.random.default_rng([a.seed, 3])
    lengths = sorted({q["prompt"] for q in deck})
    for p in lengths:
        srv.generate(warm_rng.integers(1, cfg.vocab_size, size=(b, p)
                                       ).astype(np.int32),
                     tr["warmup_new_tokens"])
    trace_dir = H.TRACE_DIR / a.workload
    if a.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        bench_trace.start(trace_dir)
    setup_s = time.perf_counter() - a.t_start
    c0 = compiles.count

    # ---- window ----
    outs = []
    t0 = time.perf_counter()
    with a.spans.span("bench.window"):
        for q, prompt in zip(deck, prompts):
            with a.spans.span("bench.batch"):
                outs.append(srv.generate(prompt, max(q["answers"])))
    t1 = time.perf_counter()
    if a.trace:
        bench_trace.stop()
    in_window = compiles.count - c0

    params_abs = H.abstract(srv.params)
    cache_abs = jax.eval_shape(lambda: init_cache(cfg, b, tr["max_len"]))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    footprints = {f"prefill_{p}": H.footprint(
        prefill, params_abs, cache_abs,
        {"tokens": jax.ShapeDtypeStruct((b, p), jnp.int32)}) for p in lengths}
    footprints["decode"] = H.footprint(decode, params_abs, cache_abs,
                                       {"tokens": tok},
                                       jax.ShapeDtypeStruct((), jnp.int32))
    H.log(f"compiles after the window, for the footprints: "
          f"{compiles.count - c0 - in_window}")
    device = H.device_info(a.devices, footprints)

    answered = sum(sum(q["answers"]) for q in deck)
    failed = sum(len(q["answers"]) for o, q in zip(outs, deck)
                 if o["tokens"].shape != (len(q["answers"]), max(q["answers"])))
    metrics = {"serve_tokens_per_s": answered / (t1 - t0), "setup_s": setup_s}
    steps = [max(q["answers"]) for q in deck]
    ctx: Dict[str, Any] = {
        "window_s": t1 - t0, "compiles_in_window": in_window,
        "prefill_ms": 1e3 * statistics.mean(o["prefill_s"] for o in outs),
        "decode_steps": sum(steps),
        # each batch's prompt length and decode calls: call i writes at
        # position prompt + i
        "decode_positions": [(q["prompt"], n) for q, n in zip(deck, steps)],
        "batch": b, "config": a.config, "traffic": tr,
        "requests": sum(len(q["answers"]) for q in deck),
        "answered_tokens": answered, "generated_tokens": b * sum(steps),
    }

    # ---- check: the served answers against the plain reference ----
    t_check = time.perf_counter()
    srv.params = None
    del srv
    gc.collect()
    picks = check_sample(a.seed, deck, tr["check_min_tokens"])
    gaps = a.reference.served_gaps(
        a.config, H.key_from_seed(a.seed),
        [prompts[j][r] for j, r in picks],
        [outs[j]["tokens"][r, : deck[j]["answers"][r]] for j, r in picks],
        control=a.control)
    readings = {"served_logit_gap": gaps["served_logit_gap"]}
    if a.control:
        ctx["control"] = H.control_verdict(
            {"served_logit_gap": gaps["control_logit_gap"]}, a.limits)
    ctx["readings"] = dict(readings, checked_tokens=gaps["tokens"])
    ctx["check_s"] = time.perf_counter() - t_check
    result = H.RunResult(metrics=metrics, checks=H.checks_from(readings, a.limits),
                         attempted=ctx["requests"], failed=failed, device=device,
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
