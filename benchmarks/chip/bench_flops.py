"""Operations and bytes that the benchmark's models need, from shapes alone.

The counts read the configuration files (the source's key names), not the
program, so a change to the program cannot change its own yardstick.  Only
multiply-adds of matrix products count (2 operations each); norms,
activations and other elementwise work are left out, so every share built
on these counts is a lower bound on what the program really does.

mamba2 (arXiv:2405.21060, SSD with chunk length L): per token and layer the
forward pass needs
    in_proj      2 d (2 di + 2 G N + H)
    conv         2 W (di + 2 G N)            depthwise, width W
    C.B          G N (L + 1)                 causal half of the L x L chunk
    intra y      H P (L + 1)                 causal half
    chunk state  2 H P N
    inter y      2 H P N
    state pass   2 H P N / L                 once per chunk
    out_proj     2 di d
and the tied LM head 2 d V.  A training step counts three forward passes
(the backward pass is two), and nothing that remat recomputes.

stablelm (dense decoder, MHA): a decode step of a sequence at position p
(the new token attends to p + 1 entries) needs 2 x (non-embedding weights)
+ 4 H dh (p + 1) per layer + 2 d V.  Its bytes are every weight once, the
embedding rows it gathers, the KV entries up to p + 1 and the new entry's
write; not the whole `max_len` cache that the program reads.
"""
from __future__ import annotations

from typing import Dict, Iterable


def mamba2_dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    return {"d": d, "di": di, "H": di // cfg["headdim"], "P": cfg["headdim"],
            "N": cfg["d_state"], "G": cfg["ngroups"], "W": cfg["d_conv"],
            "L": cfg["chunk_size"], "V": cfg["vocab_size"],
            "layers": cfg["n_layer"]}


def mamba2_layer_fwd_flops_per_token(cfg: Dict) -> Dict[str, float]:
    m = mamba2_dims(cfg)
    d, di, H, P, N, G, W, L = (m[k] for k in "d di H P N G W L".split())
    return {
        "in_proj": 2.0 * d * (2 * di + 2 * G * N + H),
        "conv": 2.0 * W * (di + 2 * G * N),
        "ssd_cb": 1.0 * G * N * (L + 1),
        "ssd_intra": 1.0 * H * P * (L + 1),
        "ssd_state": 2.0 * H * P * N,
        "ssd_inter": 2.0 * H * P * N,
        "ssd_pass": 2.0 * H * P * N / L,
        "out_proj": 2.0 * di * d,
    }


def mamba2_fwd_flops_per_token(cfg: Dict) -> float:
    layer = sum(mamba2_layer_fwd_flops_per_token(cfg).values())
    head = 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return cfg["n_layer"] * layer + head


def mamba2_train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    """Forward plus backward of one step, no recompute."""
    return 3.0 * mamba2_fwd_flops_per_token(cfg) * batch * seq


def mamba2_param_count(cfg: Dict) -> int:
    m = mamba2_dims(cfg)
    d, di, H, N, G, W = (m[k] for k in "d di H N G W".split())
    conv_dim = di + 2 * G * N
    per_layer = (d                                  # norm
                 + d * (2 * di + 2 * G * N + H)     # in_proj
                 + W * conv_dim + conv_dim          # conv weight, bias
                 + 3 * H                            # A_log, D, dt_bias
                 + di                               # gated norm
                 + di * d)                          # out_proj
    return m["layers"] * per_layer + m["V"] * d + d  # + tied embed, final norm


def stablelm_dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": h, "Hkv": cfg["num_key_value_heads"], "dh": d // h,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def stablelm_layer_weights(cfg: Dict) -> int:
    m = stablelm_dims(cfg)
    d, H, Hkv, dh, ff = (m[k] for k in "d H Hkv dh ff".split())
    attn = d * (H + 2 * Hkv) * dh + H * dh * d
    mlp = 3 * d * ff
    norms = 2 * 2 * d                                # two LayerNorms
    return attn + mlp + norms


def stablelm_param_count(cfg: Dict) -> int:
    m = stablelm_dims(cfg)
    embed = m["V"] * m["d"] * (1 if cfg["tie_word_embeddings"] else 2)
    return m["layers"] * stablelm_layer_weights(cfg) + embed + 2 * m["d"]


def stablelm_decode_step(cfg: Dict, positions: Iterable[int],
                         bytes_per_weight: int = 2, bytes_per_kv: int = 2
                         ) -> Dict[str, float]:
    """FLOPs and needed HBM bytes of one decode step of a batch whose rows
    sit at `positions` (the index the new token is written to)."""
    m = stablelm_dims(cfg)
    d, H, Hkv, dh, ff, V, nl = (m[k] for k in "d H Hkv dh ff V layers".split())
    pos = [int(p) for p in positions]
    b = len(pos)
    matmul_per_token = nl * (d * (H + 2 * Hkv) * dh + H * dh * d
                             + 3 * d * ff) + d * V
    attn = sum(nl * 4.0 * H * dh * (p + 1) for p in pos)
    flops = 2.0 * matmul_per_token * b + attn
    weights = (nl * stablelm_layer_weights(cfg) + 2 * d + d * V) \
        * bytes_per_weight
    gathered = b * d * bytes_per_weight
    kv_entry = 2 * nl * Hkv * dh * bytes_per_kv     # K and V, every layer
    kv = sum((p + 1) * kv_entry for p in pos) + b * kv_entry  # read + write
    return {"flops": flops, "bytes": float(weights + gathered + kv)}
