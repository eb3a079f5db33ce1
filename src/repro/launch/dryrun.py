import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct inputs (zero allocation), record
memory_analysis / cost_analysis / collective-bytes for the roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod | --both-meshes]

Results are appended incrementally to benchmarks/results/dryrun.json so an
interrupted sweep resumes where it left off.
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config, shapes_for
from ..configs.base import InputShape, ModelConfig
from ..optim import AdamWConfig
from ..runtime import sharding as sh
from ..context import activation_specs
from ..runtime.steps import (abstract_batch, abstract_cache, abstract_state,
                             make_train_step_fn, model_axes, prefill_step,
                             serve_step)
from .mesh import make_production_mesh

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results", "dryrun.json")

# HBM-bound giants keep Adam moments in bf16 (see optim.adamw)
BF16_MOMENT_ARCHS = {"deepseek-v3-671b", "jamba-1.5-large-398b",
                     "command-r-35b"}


def opt_cfg_for(arch: str) -> AdamWConfig:
    md = jnp.bfloat16 if arch in BF16_MOMENT_ARCHS else jnp.float32
    return AdamWConfig(moment_dtype=md)


# ---------------------------------------------------------------------------
# HLO collective-byte accounting
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[\w-]*\(", )
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op, by op kind.

    Result-shape bytes approximate the wire bytes per participating device:
    all-gather receives ~result, all-reduce moves ~2x operand (we count 2x),
    reduce-scatter ~operand (= result x shards, counted from the operand via
    the paired all-gather convention — we use result and note the approx).
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        tuple_shapes, single_shape, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _shape_bytes(tuple_shapes or single_shape or "")
        if kind == "all-reduce":
            nbytes *= 2
        out[kind] += nbytes
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(cfg: ModelConfig, shape: InputShape, mesh):
    """Returns (jitted_fn, example_args_sds) for one cell."""
    policy = sh.ShardingPolicy()
    axes = model_axes(cfg)
    opt_cfg = opt_cfg_for(cfg.name)

    if shape.kind == "train":
        state_sds = abstract_state(cfg, opt_cfg)
        pspec = sh.param_specs(state_sds["params"], axes, mesh, policy)
        state_shard = {
            "params": jax.tree_util.tree_map(
                lambda s: jax.NamedSharding(mesh, s), pspec,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
            "opt": {
                "m": jax.tree_util.tree_map(
                    lambda s: jax.NamedSharding(mesh, s), pspec,
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
                "v": jax.tree_util.tree_map(
                    lambda s: jax.NamedSharding(mesh, s), pspec,
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
                "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            },
        }
        batch_sds = abstract_batch(cfg, shape)
        bshard = {k: sh.batch_shardings(mesh, shape).get(
                      k, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
                  for k in batch_sds}
        fn = jax.jit(make_train_step_fn(cfg, opt_cfg),
                     in_shardings=(state_shard, bshard),
                     out_shardings=(state_shard, None),
                     donate_argnums=(0,))
        return fn, (state_sds, batch_sds)

    # serve paths
    params_sds = abstract_state(cfg, opt_cfg)["params"]
    pspec = sh.param_specs(params_sds, axes, mesh, policy)
    pshard = jax.tree_util.tree_map(
        lambda s: jax.NamedSharding(mesh, s), pspec,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    cache_sds = abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cshard = sh.cache_shardings(cfg, mesh, shape.global_batch, shape.seq_len)

    if shape.kind == "prefill":
        batch_sds = abstract_batch(cfg, shape)
        bshard = {k: sh.batch_shardings(mesh, shape).get(
                      k, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
                  for k in batch_sds}
        fn = jax.jit(lambda p, c, b: prefill_step(p, c, b, cfg),
                     in_shardings=(pshard, cshard, bshard),
                     out_shardings=(None, cshard),
                     donate_argnums=(1,))
        return fn, (params_sds, cache_sds, batch_sds)

    # decode: one new token against a cache of seq_len
    batch_sds = abstract_batch(cfg, shape, for_decode=True)
    bshard = {k: sh.batch_shardings(mesh, shape, for_decode=True).get(
                  k, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
              for k in batch_sds}
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(lambda p, c, b, pos: serve_step(p, c, b, pos, cfg),
                 in_shardings=(pshard, cshard, bshard, None),
                 out_shardings=(None, cshard),
                 donate_argnums=(1,))
    return fn, (params_sds, cache_sds, batch_sds, pos_sds)


def run_cell(arch: str, shape: InputShape, *, multi_pod: bool,
             keep_hlo: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    act = sh.activation_specs_for(mesh, shape, cfg)
    with jax.set_mesh(mesh), activation_specs(act):
        fn, args = build_cell(cfg, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape.name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "devices": int(n_dev),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", -1)),
        },
        "ok": True,
    }
    if keep_hlo:
        rec["hlo_path"] = save_hlo(arch, shape.name, rec["mesh"], hlo)
    return rec


def save_hlo(arch: str, shape: str, mesh: str, hlo: str) -> str:
    d = os.path.join(os.path.dirname(RESULTS), "hlo")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{arch}_{shape}_{mesh}.hlo.txt")
    with open(path, "w") as f:
        f.write(hlo)
    return path


# ---------------------------------------------------------------------------
# sweep driver with incremental JSON persistence
# ---------------------------------------------------------------------------

def load_results() -> Dict[str, Any]:
    if os.path.exists(RESULTS):
        with open(RESULTS) as f:
            return json.load(f)
    return {}


def store_result(key: str, rec: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    res = load_results()
    res[key] = rec
    tmp = RESULTS + ".tmp"
    with open(tmp, "w") as f:
        json.dump(res, f, indent=1, sort_keys=True)
    os.replace(tmp, RESULTS)


def cell_key(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}|{shape}|{'2x16x16' if multi_pod else '16x16'}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    done = load_results()

    total = ok = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = shapes_for(cfg)
        if args.shape:
            shapes = [s for s in shapes if s.name == args.shape]
        for shp in shapes:
            for mp in meshes:
                key = cell_key(arch, shp.name, mp)
                total += 1
                if not args.force and key in done and done[key].get("ok"):
                    print(f"[cached] {key}")
                    ok += 1
                    continue
                print(f"[run]    {key} ...", flush=True)
                try:
                    rec = run_cell(arch, shp, multi_pod=mp,
                                   keep_hlo=args.keep_hlo)
                    ok += 1
                    print(f"         flops/dev={rec['flops_per_device']:.3e} "
                          f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
                          f"args={rec['memory']['argument_bytes']/2**30:.2f}GiB "
                          f"compile={rec['compile_s']}s")
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shp.name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"         FAILED: {rec['error']}")
                store_result(key, rec)
    print(f"\n{ok}/{total} cells green")
    if ok < total:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
