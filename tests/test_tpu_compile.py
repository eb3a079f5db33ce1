"""Compile the main-path programs for a TPU v5e chip that is described, not
attached: the trainer's step and the server's prefill and decode at full
width (stablelm-3b, and the DeepSeek-V2-Lite chip share at its cell's
shapes).  Nothing runs; the TPU compiler refuses what the chip could not
run (tiling, VMEM, HBM).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.models import init_cache
from repro.optim import AdamWConfig
from repro.runtime.steps import (abstract_batch, abstract_state,
                                 make_train_step_fn, prefill_step, serve_step)

HBM_BYTES = 16 * 2**30  # one v5e chip
# the rag-decode prefill (4,096 tokens, batch 16) when each 256-query chunk
# scored all 4,096 keys: skipping the future key blocks must not cost memory
DEEPSEEK_PREFILL_BYTES = 14_555_475_456


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_mamba2_train_step_fits_one_chip(one_chip):
    """The step chip_smoke.py trains: mamba2-130m at full width, 8 x 2048."""
    cfg = get_config("mamba2-130m")
    opt_cfg = AdamWConfig(total_steps=6, warmup_steps=1)
    shape = InputShape("smoke_train", 2048, 8, "train")
    state = _on(abstract_state(cfg, opt_cfg), one_chip)
    batch = _on(abstract_batch(cfg, shape), one_chip)
    step = jax.jit(make_train_step_fn(cfg, opt_cfg), donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_stablelm_serve_steps_fit_one_chip(one_chip, phase):
    """The server chip_smoke.py runs: stablelm-3b full, batch 4, max_len 512,
    128-token prompts."""
    cfg = get_config("stablelm-3b")
    b, prompt, max_len = 4, 128, 512
    params = _on(abstract_state(cfg, AdamWConfig())["params"], one_chip)
    cache = _on(jax.eval_shape(lambda: init_cache(cfg, b, max_len)), one_chip)
    if phase == "prefill":
        fn = jax.jit(lambda p, c, x: prefill_step(p, c, x, cfg),
                     donate_argnums=(1,))
        tokens = jax.ShapeDtypeStruct((b, prompt), jnp.int32, sharding=one_chip)
        compiled = fn.lower(params, cache, {"tokens": tokens}).compile()
    else:
        fn = jax.jit(lambda p, c, x, pos: serve_step(p, c, x, pos, cfg),
                     donate_argnums=(1,))
        tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = fn.lower(params, cache, {"tokens": tokens}, pos).compile()
    assert _device_bytes(compiled) < HBM_BYTES


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def _hlo_instrs(text):
    """(name, dims, opcode, operand names) of every array-valued
    instruction, those inside fused computations included."""
    out = []
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            name, dims, op, args = m.groups()
            out.append((name, tuple(int(d) for d in dims.split(",") if d), op,
                        re.findall(r"%([\w.\-]+)", args)))
    return out


def _compile_serve_step(cfg, one_chip, phase, b, chunk, max_len):
    """The server's prefill or decode step for `cfg`, the cache donated,
    compiled for one described chip; and the abstract cache."""
    params = _on(abstract_state(cfg, AdamWConfig())["params"], one_chip)
    cache = _on(jax.eval_shape(lambda: init_cache(cfg, b, max_len)), one_chip)
    tokens = jax.ShapeDtypeStruct((b, chunk), jnp.int32, sharding=one_chip)
    if phase == "prefill":
        fn = jax.jit(lambda p, c, x: prefill_step(p, c, x, cfg),
                     donate_argnums=(1,))
        return fn.lower(params, cache, {"tokens": tokens}).compile(), cache
    fn = jax.jit(lambda p, c, x, pos: serve_step(p, c, x, pos, cfg),
                 donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return fn.lower(params, cache, {"tokens": tokens}, pos).compile(), cache


def _cache_touches(compiled, leaves, b, chunk, layer_runs):
    """Every copy, transpose or update of a stacked cache leaf [L, B, T,
    ...], of one layer's slice of it or of that slice with a unit layer
    axis, as (name, op, shape of the update); and the shapes [n, B, chunk,
    ...] of an update that writes only `chunk` positions of a run of n
    layers, n in `layer_runs` (the unrolled prefix, the scanned rest)."""
    cache_shapes, new_entries = set(), set()
    for stacked in leaves:
        layer = stacked[1:]
        cache_shapes |= {stacked, layer, (1, *layer)}
        new_entries |= {(n, b, chunk, *stacked[3:]) for n in layer_runs}
    instrs = _hlo_instrs(compiled.as_text())
    dims_of = {name: dims for name, dims, _, _ in instrs}
    touched = [(name, op, dims_of.get(args[1]) if len(args) > 1 else None)
               for name, dims, op, args in instrs if dims in cache_shapes
               and op in ("copy", "transpose", "dynamic-update-slice")]
    return touched, new_entries


@pytest.mark.parametrize("phase,chunk", [("prefill", 512), ("decode", 1)])
def test_stablelm_serve_steps_touch_only_new_cache_entries(one_chip, phase,
                                                           chunk):
    """The decode cell's shape: stablelm-3b full, batch 12, max_len 1024,
    512-token prompts, the cache donated.  The steps read the KV cache where
    it lies and write only the chunk's entries into it: no copy or transpose
    of a layer's [B, max_len, Hkv, dh] slice or of the stacked cache, and
    every update whose result has either shape writes a chunk of `chunk`
    positions.  Decode then needs under 1 GiB of scratch."""
    cfg = get_config("stablelm-3b")
    b = 12
    compiled, cache = _compile_serve_step(cfg, one_chip, phase, b, chunk, 1024)
    touched, new_entries = _cache_touches(compiled, [cache["kv"]["k"].shape],
                                          b, chunk, [cfg.n_layers])
    assert touched, "the cache update was not found in the compiled program"
    assert all(op == "dynamic-update-slice" and update in new_entries
               for _, op, update in touched), touched
    if phase == "decode":
        assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("phase,chunk", [("prefill", 4096), ("decode", 1)])
def test_deepseek_share_serve_steps_fit_and_touch_only_new_entries(
        one_chip, phase, chunk):
    """The rag-decode cell's shapes: the DeepSeek-V2-Lite chip share (8 of
    64 routed experts) at published widths, batch 16, max_len 4352,
    4096-token prompts, the latent cache donated.  Each step fits the chip;
    both read the stacked latent cache where it lies and write only the
    chunk's entries (no copy or transpose of the [27, B, max_len, 512] or
    [27, B, max_len, 64] cache or of a layer's slice of it, the prefix
    layer's included); decode needs under 64 MiB of scratch, and prefill
    no more than it took when every query chunk scored all 4,096 keys."""
    cfg = get_config("deepseek-v2-lite")
    b = 16
    compiled, cache = _compile_serve_step(cfg, one_chip, phase, b, chunk, 4352)
    touched, new_entries = _cache_touches(
        compiled, [x.shape for x in cache["mla"].values()], b, chunk,
        [cfg.moe.n_dense_prefix, cfg.n_layers - cfg.moe.n_dense_prefix])
    assert touched, "the cache update was not found in the compiled program"
    assert all(op == "dynamic-update-slice" and update in new_entries
               for _, op, update in touched), touched
    if phase == "decode":
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    else:
        assert _device_bytes(compiled) <= DEEPSEEK_PREFILL_BYTES
    assert _device_bytes(compiled) < HBM_BYTES
