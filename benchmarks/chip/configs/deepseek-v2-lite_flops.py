"""Operations and needed bytes of a decode step of the DeepSeek-V2-Lite chip
share, from the configuration file's shapes (the source's key names), and
of the two kernels the per-layer metrics time: latent attention over the
cache (`mla.attend`) and the held experts' grouped products
(`moe.experts`).

Only multiply-adds of matrix products count (2 operations each).  Decode
attention is counted in its absorbed form, as it needs to be: per head the
query's no-rope part goes into the latent space (nope x r), scores are taken
against each cached latent and rope entry (r + rope), the weighted latents
summed (r) and expanded to the value dims (r x dv).  A row at position p
attends to p + 1 entries.

Routing is data-dependent; counts take its expectation under uniform
routing.  Of B tokens' k picks each, B k held / E land on the experts held
here; an expert is hit by at least one token with probability
1 - (1 - k/E)^B, so held (1 - (1 - k/E)^B) experts' weights are read.

Bytes: every weight outside the routed experts once (the router in float32,
the rest in bfloat16), the embedding rows gathered, the hit experts'
weights, each row's latent-cache entries up to its position and its new
entry's write (bfloat16); not the whole `max_len` cache that the program
reads.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16, F32 = 2, 4


def dims(c: Dict) -> Dict[str, int]:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "ff": c["intermediate_size"], "eff": c["moe_intermediate_size"],
            "e": c["router_experts"], "held": c["n_routed_experts"],
            "k": c["num_experts_per_tok"], "shared": c["n_shared_experts"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"]}


def attn_weights(c: Dict) -> int:
    """MLA's weights of one layer (no q compression)."""
    m = dims(c)
    d, h, r, nope, rope, dv = (m[x] for x in "d h r nope rope dv".split())
    return (d * h * (nope + rope) + d * (r + rope) + r * h * nope
            + r * h * dv + h * dv * d)


def expert_weights(c: Dict) -> int:
    m = dims(c)
    return 3 * m["d"] * m["eff"]


def param_count(c: Dict) -> int:
    m = dims(c)
    d, n_moe = m["d"], m["layers"] - m["dense"]
    norms = 2 * d + m["r"]                          # two RMSNorms, kv_norm
    dense = m["dense"] * (attn_weights(c) + norms + 3 * d * m["ff"])
    moe = n_moe * (attn_weights(c) + norms + d * m["e"]
                   + (m["held"] + m["shared"]) * expert_weights(c))
    return dense + moe + 2 * m["v"] * d + d         # untied head, final norm


def held_pairs(c: Dict, batch: int) -> float:
    """Expected token-expert pairs on the held experts, one MoE layer."""
    m = dims(c)
    return batch * m["k"] * m["held"] / m["e"]


def experts_hit(c: Dict, batch: int) -> float:
    """Expected held experts hit by at least one of `batch` tokens."""
    m = dims(c)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["e"]) ** batch)


def mla_decode_attn(c: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """Scores against the cache and the new entry, softmax-weighted latent
    sum; every layer, rows at `positions`.  Bytes: the latent and rope
    entries up to each row's position, the absorbed query in and the
    latent sum out (float32)."""
    m = dims(c)
    h, r, rope, nl = m["h"], m["r"], m["rope"], m["layers"]
    pos = [int(p) for p in positions]
    entries = sum(p + 1 for p in pos)
    flops = 2.0 * nl * h * (2 * r + rope) * entries
    io = nl * len(pos) * h * (2 * r + rope) * F32
    return {"flops": flops, "bytes": float(nl * entries * (r + rope) * BF16 + io)}


def moe_experts(c: Dict, batch: int) -> Dict[str, float]:
    """The held experts' gate, up and down products over one decode step's
    `batch` tokens, every MoE layer.  Bytes: the hit experts' weights and
    the pairs' rows in and out."""
    m = dims(c)
    n_moe, d = m["layers"] - m["dense"], m["d"]
    pairs = held_pairs(c, batch)
    flops = 2.0 * n_moe * pairs * expert_weights(c)
    rows = 2 * pairs * d * BF16
    weights = experts_hit(c, batch) * expert_weights(c) * BF16
    return {"flops": flops, "bytes": float(n_moe * (weights + rows))}


def decode_step(c: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """FLOPs and needed HBM bytes of one decode step of a batch whose rows
    sit at `positions` (the index the new token is written to)."""
    m = dims(c)
    d, h, r, nope, rope, dv = (m[x] for x in "d h r nope rope dv".split())
    pos = [int(p) for p in positions]
    b, nl, n_moe = len(pos), m["layers"], m["layers"] - m["dense"]
    # per token: projections, absorption (nope x r in, r x dv out), dense
    # layer, router, shared experts, head
    proj = d * h * (nope + rope) + d * (r + rope) + h * r * (nope + dv) \
        + h * dv * d
    per_token = (nl * proj + m["dense"] * 3 * d * m["ff"]
                 + n_moe * (d * m["e"] + m["shared"] * expert_weights(c))
                 + d * m["v"])
    attn = mla_decode_attn(c, pos)
    experts = moe_experts(c, b)
    flops = 2.0 * per_token * b + attn["flops"] + experts["flops"]
    norms = 2 * d + r
    weights = (nl * (attn_weights(c) + norms) * BF16
               + m["dense"] * 3 * d * m["ff"] * BF16
               + n_moe * (d * m["e"] * F32
                          + m["shared"] * expert_weights(c) * BF16)
               + (d * m["v"] + d) * BF16)
    gathered = b * d * BF16
    cache = sum(p + 1 for p in pos) * nl * (r + rope) * BF16 \
        + b * nl * (r + rope) * BF16                # read, then the write
    hit = n_moe * experts_hit(c, b) * expert_weights(c) * BF16
    return {"flops": flops, "bytes": float(weights + gathered + cache + hit)}
