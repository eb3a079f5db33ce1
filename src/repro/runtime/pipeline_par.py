"""GPipe-style pipeline parallelism over the "pod" axis (optional).

The default multi-pod layout uses the pod axis for data parallelism; this
module offers the alternative: stages = pods, with microbatches streamed
through `shard_map` + `ppermute`.  Each stage owns a contiguous slice of the
layer stack; activations hop stage->stage over DCN once per microbatch —
bubble fraction (S-1)/(M+S-1) for S stages, M microbatches.

This is a self-contained reference implementation exercised by tests on a
host mesh; wiring it into the full train step is an opt-in config
(runtime cost/benefit shows up in the roofline collective term).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_forward(layer_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
                     stage_params: PyTree, x: jnp.ndarray, *, mesh: Mesh,
                     axis: str = "pod", n_microbatches: int = 4) -> jnp.ndarray:
    """Run x through S pipeline stages living on the `axis` mesh dimension.

    stage_params: pytree whose leaves have leading dim S (one slice per
    stage, pre-sharded over `axis`).  x: [B, ...] global batch, sharded over
    `axis` is NOT required — each microbatch visits every stage.
    Returns layer_fn applied S times (stage s applies its own params).
    """
    s_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0
    mb = b // n_microbatches

    pspec = P(axis)   # stage dim sharded: each device holds its stage slice
    xspec = P()       # activations replicated per stage group

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec, check_vma=False)
    def run(params_local, xg):
        stage = jax.lax.axis_index(axis)
        params_mine = jax.tree_util.tree_map(lambda a: a[0], params_local)
        n_ticks = n_microbatches + s_stages - 1
        perm = [(i, i + 1) for i in range(s_stages - 1)]

        def tick(carry, t):
            inflight, out = carry
            # which microbatch enters the pipe this tick (stage 0 only)
            mb_idx = jnp.clip(t, 0, n_microbatches - 1)
            enter = jax.lax.dynamic_slice_in_dim(xg, mb_idx * mb, mb, 0)
            stage_in = jnp.where(stage == 0, enter, inflight)
            y = layer_fn(params_mine, stage_in)
            # exiting microbatch index at the last stage
            exit_idx = t - (s_stages - 1)
            out = jax.lax.cond(
                (stage == s_stages - 1) & (exit_idx >= 0),
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y, jnp.clip(exit_idx, 0, n_microbatches - 1) * mb, 0),
                lambda o: o, out)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, out), None

        init = (jnp.zeros((mb,) + xg.shape[1:], xg.dtype),
                jnp.zeros_like(xg))
        (_, out), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # every stage group holds the same `out` copy at the end via psum of
        # the last stage's buffer
        mask = (stage == s_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    return run(stage_params, x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
