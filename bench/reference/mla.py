"""Float32 reference of DeepSeek-V3's dense-first layers: multi-head latent
attention (arXiv:2412.19437 §2.1.1, in its plain per-head form) and the
SwiGLU FFN, pre-norm residual, with an untied output head.

Weights arrive as a nested dict keyed like the served model's parameters
(``embed/{emb,final_norm,unemb}``, ``dense0/{ln1,attn/*,ln2,mlp/*}`` with a
leading layer axis); the benchmark draws them (``draw``) and hands the
same arrays to the program and to this reference. Departures from the
published model, shared with the program: rotary embedding without YaRN
scaling (the configuration file states YaRN at factor 1, which leaves
plain rotary embedding), rotated as two halves (a fixed permutation of
the published interleaved pairs, which random weights do not see).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.reference import common as C

NORMS = ("ln1", "ln2", "q_norm", "kv_norm", "final_norm")


def program_view(hf: Dict) -> Dict[str, object]:
    """The program's config fields that must equal the published keys."""
    check_rope_scaling(hf)
    return {"d_model": hf["hidden_size"], "num_heads": hf["num_attention_heads"],
            "d_ff": hf["intermediate_size"], "vocab_size": hf["vocab_size"],
            "num_layers": hf["num_hidden_layers"],
            "mla.kv_lora_rank": hf["kv_lora_rank"],
            "mla.q_lora_rank": hf["q_lora_rank"],
            "mla.qk_nope_dim": hf["qk_nope_head_dim"],
            "mla.qk_rope_dim": hf["qk_rope_head_dim"],
            "mla.v_head_dim": hf["v_head_dim"],
            "rope_theta": hf["rope_theta"], "rms_eps": hf["rms_norm_eps"],
            "tie_embeddings": hf["tie_word_embeddings"]}


def check_rope_scaling(hf: Dict):
    """Neither this reference nor the program applies YaRN. At ``factor``
    1 or below YaRN's frequencies, its rotary scale and its attention
    scale (``mscale_all_dim``) are those of plain rotary embedding, so a
    file stating a larger factor cannot be run."""
    rs = hf.get("rope_scaling")
    if rs and rs.get("factor", 1) > 1:
        raise ValueError(f"rope_scaling factor {rs['factor']}: neither the "
                         "program nor the reference applies YaRN")


def draw(structs, key):
    return C.draw(structs, key, NORMS)


def _layer(x, p, hf, mm):
    """One pre-norm MLA + SwiGLU layer over x (S, d), float32."""
    S = x.shape[0]
    nh = hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], \
        hf["v_head_dim"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(S)
    a = p["attn"]
    h = C.rmsnorm(x, p["ln1"], eps)
    q = mm(C.rmsnorm(mm(h, a["w_dq"]), a["q_norm"], eps), a["w_uq"])
    q = q.reshape(S, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], C.rope(q[..., dn:], pos, theta)], -1)
    ckv = C.rmsnorm(mm(h, a["w_dkv"]), a["kv_norm"], eps)
    kr = C.rope(mm(h, a["w_kr"])[:, None, :], pos, theta)      # (S, 1, dr)
    k_nope = mm(ckv, a["w_uk"]).reshape(S, nh, dn)
    v = mm(ckv, a["w_uv"]).reshape(S, nh, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (S, nh, dr))], -1)
    o = C.causal_attention(q, k, v, 1.0 / math.sqrt(dn + dr))
    x = x + mm(o.reshape(S, nh * dv), a["w_o"])
    m = p["mlp"]
    h = C.rmsnorm(x, p["ln2"], eps)
    return x + C.swiglu(h, m["w_gate"], m["w_up"], m["w_down"], mm)


@functools.partial(jax.jit, static_argnames=("hf_items", "fmt"))
def _run_layer(x, p, hf_items, fmt):
    with jax.default_matmul_precision("highest"):
        return _layer(x, p, dict(hf_items), C.control_matmul(fmt))


def hidden(params, hf: Dict, tokens, fmt: Optional[str] = None):
    """Final-normed hidden states (S, d) float32 for ``tokens`` (S,), layer
    by layer; ``fmt`` names the control's operand format (None: float32).
    S must divide by the attention query block."""
    items = tuple(sorted((k, v) for k, v in hf.items()
                         if isinstance(v, (int, float, bool))))
    x = params["embed"]["emb"][tokens].astype(C.F32)
    layers = params["dense0"]
    for i in range(hf["num_hidden_layers"]):
        p = jax.tree.map(lambda w: w[i], layers)
        x = _run_layer(x, p, items, fmt)
    return C.rmsnorm(x, params["embed"]["final_norm"], hf["rms_norm_eps"])
