"""Model FLOPs of serving work, from a configuration file's published keys.

The arithmetic follows ``src/repro/launch/costs.py`` (GEMM FLOPs are twice
the matmul parameters per token; attention is ``2 * keys * heads * (qk +
v)`` per token and layer), restated over the Hugging Face keys so that the
benchmark's yardstick does not move with the program's own config code.

Attention is counted in its plain (per-head) form for MLA too: model
FLOPs, not the absorbed form the decode kernel runs. MoE layers count the
experts a token is routed to, its shared experts and the router.
"""
from __future__ import annotations

from typing import Dict


def _moe_layer(hf: Dict, layer: int) -> bool:
    if "n_routed_experts" in hf:                      # DeepSeek
        return (layer >= hf.get("first_k_dense_replace", 0)
                and hf["n_routed_experts"] > 0)
    if "num_experts" in hf:                           # Qwen-MoE
        return layer not in hf.get("mlp_only_layers", [])
    return False


def _attn_dims(hf: Dict):
    """(heads, qk dim, v dim)."""
    nh = hf["num_attention_heads"]
    if "kv_lora_rank" in hf:
        return nh, hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"], \
            hf["v_head_dim"]
    hd = hf.get("head_dim") or hf["hidden_size"] // nh
    return nh, hd, hd


def attn_params(hf: Dict) -> int:
    """Matmul parameters of one attention layer."""
    d = hf["hidden_size"]
    nh, hq, hv = _attn_dims(hf)
    if "kv_lora_rank" in hf:
        r, qr = hf["kv_lora_rank"], hf["q_lora_rank"]
        return (d * qr + qr * nh * hq + d * r + d * hf["qk_rope_head_dim"]
                + r * nh * (hf["qk_nope_head_dim"] + hv) + nh * hv * d)
    nkv = hf["num_key_value_heads"]
    return d * hq * (nh + 2 * nkv) + nh * hv * d


def ffn_params(hf: Dict, layer: int) -> int:
    """Matmul parameters one token runs through in one layer's FFN."""
    d = hf["hidden_size"]
    if not _moe_layer(hf, layer):
        return 3 * d * hf["intermediate_size"]
    f, k = hf["moe_intermediate_size"], hf["num_experts_per_tok"]
    experts = hf.get("n_routed_experts", hf.get("num_experts"))
    shared = hf.get("n_shared_experts", 0)
    return 3 * d * f * (k + shared) + d * experts


def layer_flops(hf: Dict, layer: int, keys: int) -> float:
    """FLOPs of one token in one layer, attending over ``keys`` keys."""
    nh, hq, hv = _attn_dims(hf)
    return (2.0 * (attn_params(hf) + ffn_params(hf, layer))
            + 2.0 * keys * nh * (hq + hv))


def unembed_flops(hf: Dict) -> float:
    return 2.0 * hf["hidden_size"] * hf["vocab_size"]


def prompt_flops(hf: Dict, start: int, end: int) -> float:
    """Prompt positions ``[start, end)`` computed (position ``i`` attends
    over ``i + 1`` keys), without the unembedding."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) / 2.0
    nh, hq, hv = _attn_dims(hf)
    total = 0.0
    for layer in range(hf["num_hidden_layers"]):
        total += (2.0 * (attn_params(hf) + ffn_params(hf, layer)) * n
                  + 2.0 * keys * nh * (hq + hv))
    return total


def decode_flops(hf: Dict, context: int) -> float:
    """One generated token at ``context`` keys, with its unembedding."""
    return (sum(layer_flops(hf, i, context)
                for i in range(hf["num_hidden_layers"]))
            + unembed_flops(hf))
