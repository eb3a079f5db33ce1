"""Sharding policy: logical parameter/cache axes -> mesh axes.

One table drives FSDP x TP x EP for every architecture:

  logical axis          mesh axis       role
  -----------------     -----------     ------------------------------
  vocab, heads, mlp,    "model"         tensor / expert parallelism
  kv_heads, experts
  embed                 "data"          FSDP (ZeRO-3 weight sharding;
                                        all-gathered on use by GSPMD)
  lora, head_dim, ...   (replicated)    small dims

A dim is only sharded when divisible by the axis size (e.g. kv_heads=8 on a
16-way model axis stays replicated — Megatron-style KV duplication for GQA).
Batch shards over ("pod","data"); for long-context single-sequence shapes the
SEQUENCE dim shards over "data" instead (sequence parallelism).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import InputShape, ModelConfig

PyTree = Any

LOGICAL_TO_MESH: Dict[str, Optional[str]] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "embed": "data",          # FSDP
    "lora": None,
    "head_dim": None,
    "experts_nosplit": None,
    "heads_nosplit": None,
    None: None,
}


@dataclass(frozen=True)
class ShardingPolicy:
    fsdp: bool = True                  # shard "embed" over data
    fsdp_axes: Tuple[str, ...] = ("data",)
    model_axes: Tuple[str, ...] = ("model",)

    def mesh_axes_for(self, logical: Optional[str]) -> Optional[Tuple[str, ...]]:
        tgt = LOGICAL_TO_MESH.get(logical)
        if tgt == "data":
            return self.fsdp_axes if self.fsdp else None
        if tgt == "model":
            return self.model_axes
        return None


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec_for(axes_entry: Tuple, shape: Tuple[int, ...], mesh: Mesh,
             policy: ShardingPolicy) -> P:
    """Build a PartitionSpec for one param given its logical axes + shape.
    Dims that do not divide evenly stay replicated."""
    parts = []
    used = set()
    for dim, logical in enumerate(axes_entry):
        target = policy.mesh_axes_for(logical)
        if target is None or any(t in used for t in target):
            parts.append(None)
            continue
        if shape[dim] % _axis_size(mesh, target) != 0:
            parts.append(None)
            continue
        parts.append(target if len(target) > 1 else target[0])
        used.update(target)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _lookup_axes(axes_tree: Any, keypath) -> Optional[Tuple]:
    node = axes_tree
    for k in keypath:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "idx", None)
        try:
            node = node[key]
        except (KeyError, IndexError, TypeError):
            return None
    return node if isinstance(node, tuple) else None


def param_specs(params: PyTree, axes_tree: PyTree, mesh: Mesh,
                policy: ShardingPolicy, *, stacked_prefix: int = 1) -> PyTree:
    """PartitionSpec tree matching `params`.

    Stacked (scan-over-layers) params have a leading layer dim not present in
    the logical axes tuple; it is detected by rank mismatch and treated as
    replicated (dim 0 = layers).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for kp, leaf in flat:
        ax = _lookup_axes(axes_tree, kp)
        shape = np.shape(leaf) if not hasattr(leaf, "shape") else leaf.shape
        if ax is None:
            specs.append(P())
            continue
        extra = len(shape) - len(ax)
        ax_full = (None,) * extra + tuple(ax)
        specs.append(spec_for(ax_full, tuple(shape), mesh, policy))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params: PyTree, axes_tree: PyTree, mesh: Mesh,
                    policy: ShardingPolicy) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(params, axes_tree, mesh, policy),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh, global_batch: int, seq_len: int) -> P:
    """Shard batch over (pod, data); if the batch is too small (long-context
    decode), fall back to sequence sharding over the same axes (SP)."""
    ba = batch_axes(mesh)
    n = _axis_size(mesh, ba)
    if global_batch % n == 0:
        return P(ba, None)
    if seq_len % n == 0:
        return P(None, ba)
    return P()


def activation_specs_for(mesh: Mesh, shape: InputShape,
                         cfg: Optional[ModelConfig] = None
                         ) -> Dict[str, Optional[P]]:
    """Named activation specs for the cell (see repro.context):
    'bsd' residual stream; 'heads'/'kv' attention-interior layouts (heads
    over the model axis, FULL sequence) — the Megatron seq<->head transition.
    """
    bsd = activation_spec_for(mesh, shape)
    m = mesh.shape.get("model", 1)
    bsp = batch_spec(mesh, shape.global_batch, shape.seq_len)
    bdim = tuple(bsp)[0] if len(tuple(bsp)) else None
    heads = kv = None
    if cfg is not None and m > 1 and shape.kind in ("train", "prefill"):
        # the seq->head transition is only coherent when BOTH q and kv heads
        # can take the model axis; constraining q alone while k/v stay
        # seq-sharded measurably REGRESSES (command-r train collective
        # 46.5s -> 178.9s, §Perf iter-6) because attention then mixes
        # full-seq q against seq-sharded k/v every chunk
        if cfg.n_heads % m == 0 and cfg.n_kv_heads % m == 0:
            heads = P(bdim, None, "model", None)
            kv = P(bdim, None, "model", None)
    # FFN [B,S,ff] intermediates: token-sharded in train/prefill (weights
    # gathered, not activations); decode must NOT constrain them — forcing
    # full-ff layouts on [B,1,ff] regressed every decode cell (§Perf iter-7)
    bsf = bsd if shape.kind in ("train", "prefill") else None
    # NOTE (§Perf iter-4, REFUTED): constraining the MoE dispatch buffers to
    # P("model", None, None) made GSPMD replicate the data-dependent scatter
    # on every shard and mask+all-reduce the result (measured 2.2x worse:
    # collective 43s->96s, compute 0.56s->4.0s on deepseek-v2-lite train_4k).
    # A ragged shard_map all-to-all is the correct implementation; until
    # then the capacity dispatch's buffers stay unconstrained.
    return {"bsd": bsd, "bsf": bsf, "heads": heads, "kv": kv}


def activation_spec_for(mesh: Mesh, shape: InputShape) -> P:
    """[B,S,D] residual-stream spec.  Train/prefill additionally shard the
    SEQUENCE dim over "model" (Megatron-style sequence parallelism): the
    per-layer saved carries shrink by the model-axis size; attention/FFN
    gather internally (visible as all-gathers in the roofline collectives).
    Decode steps (S=1) keep the batch-only layout."""
    bsp = batch_spec(mesh, shape.global_batch, shape.seq_len)
    m = mesh.shape.get("model", 1)
    if shape.kind in ("train", "prefill") and m > 1 and shape.seq_len % m == 0:
        parts = list(bsp) + [None] * (2 - len(bsp))
        if parts[1] is None:       # seq dim free -> give it the model axis
            parts[1] = "model"
        return P(*parts, None)
    return P(*bsp, None)


def batch_shardings(mesh: Mesh, shape: InputShape, *, for_decode: bool = False
                    ) -> Dict[str, NamedSharding]:
    if for_decode:
        # decode feeds [B, 1] token arrays: batch over data axes when
        # divisible, else replicated (long-context B=1: the CACHE is what
        # gets sequence-sharded, not the one-token input)
        ba = batch_axes(mesh)
        n = _axis_size(mesh, ba)
        sp = P(ba, None) if shape.global_batch % n == 0 else P()
    else:
        sp = batch_spec(mesh, shape.global_batch, shape.seq_len)
    full = NamedSharding(mesh, sp)
    return {
        "tokens": full, "labels": full, "loss_mask": full,
        "embeds": NamedSharding(mesh, P(*sp, None)),
    }


def cache_specs(cfg: ModelConfig, mesh: Mesh, batch: int, seq_len: int
                ) -> Dict[str, Any]:
    """PartitionSpecs for the serve cache pytree (structure mirrors
    models.transformer.init_cache)."""
    ba = batch_axes(mesh)
    n = _axis_size(mesh, ba)
    bdim = ba if batch % n == 0 else None
    # sequence dim of the KV cache: shard over data axes when batch can't be
    sdim = None if bdim is not None else ba
    m = mesh.shape.get("model", 1)

    def kv():
        # [L, B, S, Hkv, dh]: batch over data axes when divisible; kv heads
        # over model when divisible, else the sequence dim takes the model
        # axis (paged-style cache sharding) so the cache still fits
        hd = "model" if (cfg.n_kv_heads % m == 0 and m > 1) else None
        sd = tuple(sdim) if sdim else ()
        if hd is None and m > 1 and seq_len % m == 0:
            sd = sd + ("model",)
        sd = sd or None
        return {"k": P(None, bdim, sd, hd, None),
                "v": P(None, bdim, sd, hd, None)}

    if cfg.family == "ssm":
        dm_heads = (cfg.ssm.expand * cfg.d_model) // cfg.ssm.head_dim
        hspec = "model" if dm_heads % m == 0 else None
        conv_dim = cfg.ssm.expand * cfg.d_model + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
        cspec = "model" if conv_dim % m == 0 else None
        return {"ssm_state": {
            "conv": P(None, bdim, None, cspec),      # [L,B,W-1,C]
            "ssm": P(None, bdim, hspec, None, None),  # [L,B,H,P,N]
        }}
    if cfg.family == "hybrid":
        dm_heads = (cfg.ssm.expand * cfg.d_model) // cfg.ssm.head_dim
        hspec = "model" if dm_heads % m == 0 else None
        conv_dim = cfg.ssm.expand * cfg.d_model + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
        cspec = "model" if conv_dim % m == 0 else None
        return {
            "kv": kv(),
            "conv": P(None, None, bdim, None, cspec),    # [NB,7,B,W-1,C]
            "ssm": P(None, None, bdim, hspec, None, None),
        }
    if cfg.mla is not None:
        lspec = "model" if cfg.mla.kv_lora_rank % m == 0 else None
        rspec = "model" if cfg.mla.qk_rope_dim % m == 0 else None
        return {"mla": {
            "ckv": P(None, bdim, sdim if lspec is None else None, lspec),
            "krope": P(None, bdim, sdim if rspec is None else None, rspec),
        }}
    return {"kv": kv()}


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, seq_len: int):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        cache_specs(cfg, mesh, batch, seq_len),
        is_leaf=lambda x: isinstance(x, P))
