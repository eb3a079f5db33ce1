#!/usr/bin/env python3
"""Whether a MoE cell's `correct` sees a fault in the program's expert layer,
on the chip, at the cell's own size: for each seed and each fault, one run
of the cell (a short window is enough) with the fault planted in
`repro.models.layers`, judged by the cell's committed limits.  All runs
share one process; each fault compiles its own programs.  The benchmark's
runs never call this.

    python3 benchmarks/chip/plant_moe.py --workload deepseek-v2-lite.rag-decode \
        --seeds 11,12 --seconds 10 [--sound]

Faults:
  capacity    each held expert takes at most ceil(t k / n_experts * 1.25)
              of a call's pairs and drops the rest (2 at a decode step of
              16 tokens and 64 experts): the dispatch the layer had before
              it was dropless
  zero_held   the held experts' part is zero; only the shared experts add
  shifted     the grouped products' group sizes rotated by one expert, so
              each expert's rows meet another expert's weights

Prints one JSON line per run: {"fault", "seed", "correct", "readings",
"serve_tokens_per_s", "seconds"}.  Exits 1 if a sound run is not correct
or a fault comes out correct.

With --layer, no cell runs: the program's held-expert part of the first
MoE layer (its own weights from the seed, at the configuration's widths)
against the reference's dense float32 part, for the same routing, on
random unit-variance inputs in the cell's call shapes: `decode`, batch
sized calls of one token each (64 of them), and `prefill`, one call of
batch x the longest prompt bucket.  Each reading is the relative RMS
error ||program - reference|| / ||reference||, for sound and each fault:
a number that sees what the served logits may not.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench_harness as H  # noqa: E402


def faults():
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L
    dropless, ragged_dot = L._dropless, jax.lax.ragged_dot

    def restore():
        L._dropless, jax.lax.ragged_dot = dropless, ragged_dot

    def capacity():
        L._dropless = L._with_capacity

    def zero_held():
        L._dropless = lambda p, xt, *a: jnp.zeros(xt.shape, jnp.float32)

    def shifted():
        jax.lax.ragged_dot = lambda x, w, g, **kw: ragged_dot(
            x, w, jnp.roll(g, 1), **kw)
    return restore, {"capacity": capacity, "zero_held": zero_held,
                     "shifted": shifted}


def layer_gaps(spec, cell, seed: int, planted, restore, runs):
    """The --layer readings: one dict per run in `runs`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_model
    from repro.models import layers as L
    c = H.load_config(spec, cell["config"])
    cfg, mo = H.program_config(c), H.program_config(c).moe
    ref = H.load_reference(spec, cell["config"])
    tr = H.load_traffic(cell["traffic"])
    key = H.key_from_seed(seed)
    params = jax.jit(lambda k: init_model(cfg, k)[0])(key)
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["ffn"])
    del params
    f32 = jnp.float32
    w = {"router": p["router"], "gate": p["wi_gate"].astype(f32),
         "up": p["wi_up"].astype(f32), "down": p["wo"].astype(f32)}
    b, t, k = tr["batch"], tr["prompt_buckets"][-1], mo.top_k
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, t, cfg.d_model)
                          ).astype(jnp.bfloat16)

    @jax.jit
    def reference(w, x):
        with jax.default_matmul_precision("highest"):
            xf = x.astype(f32)
            weight, scores = ref.routing(xf, w, c)
            top_w, top_idx = jax.lax.top_k(scores, k)
            y = sum(ref._swiglu(xf, {n: w[n][j] for n in ("gate", "up", "down")},
                                "f32") * weight[..., j:j + 1]
                    for j in range(weight.shape[-1]))
        return y, top_idx, top_w * mo.router_scale

    shapes = {"decode": x[:, :64].transpose(1, 0, 2), "prefill": x}
    want = {n: reference(w, v) for n, v in shapes.items()}
    out = []
    for name in runs:
        restore()
        if name != "sound":
            planted[name]()

        def program(p, xs, idx, tw):   # traced anew: takes the planted code
            d = xs.shape[-1]
            return jax.lax.map(
                lambda a: L._dropless(p, a[0].reshape(-1, d),
                                      a[1].reshape(-1, k), a[2].reshape(-1, k),
                                      mo), (xs, idx, tw))
        row = {"fault": name, "seed": seed}
        for n, v in shapes.items():
            y, idx, tw = want[n]
            calls = v if n == "decode" else v[None]
            got = jax.jit(program)(p, calls,
                                   idx.reshape(calls.shape[:-1] + (k,)),
                                   tw.reshape(calls.shape[:-1] + (k,)))
            y = np.asarray(y, np.float64).reshape(-1, cfg.d_model)
            g = np.asarray(got, np.float64).reshape(-1, cfg.d_model)
            row[f"{n}_rel_gap"] = float(np.linalg.norm(g - y) / np.linalg.norm(y))
        out.append(row)
    restore()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sound", action="store_true",
                    help="also one run with no fault, per seed")
    ap.add_argument("--layer", action="store_true",
                    help="compare the expert layer alone, not the cell")
    args = ap.parse_args()
    spec = H.benchmark_spec()
    cell = H.find_cell(spec, args.workload)
    traffic = H.load_traffic(cell["traffic"])
    devices = H.require_chip(cell["chips"])
    H.use_compile_cache()
    runner = H.load_runner(traffic["kind"])
    restore, planted = faults()
    runs = (["sound"] if args.sound else []) + list(planted)
    bad = False
    if args.layer:
        for seed in (int(s) for s in args.seeds.split(",")):
            for row in layer_gaps(spec, cell, seed, planted, restore, runs):
                print(json.dumps(row), flush=True)
        return 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in runs:
            restore()
            if name != "sound":
                planted[name]()
            t0 = time.perf_counter()
            r = runner.run(H.RunArgs(
                workload=args.workload, seed=seed, seconds=args.seconds,
                trace=False, config=H.load_config(spec, cell["config"]),
                traffic=traffic, limits=H.load_checks(args.workload),
                reference=H.load_reference(spec, cell["config"]),
                devices=devices, t_start=t0))
            print(json.dumps({
                "fault": name, "seed": seed, "correct": r.correct,
                "readings": r.ctx["readings"],
                "serve_tokens_per_s": r.metrics["serve_tokens_per_s"],
                "seconds": time.perf_counter() - t0}, default=float),
                flush=True)
            bad = bad or r.correct != (name == "sound")
    restore()
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
