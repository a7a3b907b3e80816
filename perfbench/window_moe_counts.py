"""Operations a token NEEDS and bytes a decode step's attention MUST MOVE of a
decoder of window and full attention layers with routed experts, from the
configuration's shapes alone: the same work whatever implements it
(readers/mfu_serve_looped.py through the configuration's `flops` entry;
readers/window_attention_decode_roofline.py)."""

from __future__ import annotations

from typing import Sequence

from perfbench import rooflines

BYTES = {"bfloat16": 2, "float32": 4}


def _attention(hidden_size, num_attention_heads, num_key_value_heads, head_dim):
    """q, k, v, the output gate and o."""
    return hidden_size * head_dim * (3 * num_attention_heads + 2 * num_key_value_heads)


def _swiglu(hidden_size, width):
    return 3 * hidden_size * width             # gate, up, down


def window_moe_flops_per_token(
    hidden_size, num_attention_heads, num_key_value_heads, head_dim, n_dense_layers,
    n_moe_layers, intermediate_size, moe_intermediate_size, num_experts, num_experts_per_tok,
    num_shared_experts, vocab_size,
) -> float:
    """2 x the matrix parameters a token multiplies with: every layer's
    attention projections, the dense layers' MLP, each expert layer's
    router, its `num_experts_per_tok` chosen experts and its shared ones, and
    the untied head. Attention's products with the context are left out, as
    the other served models' counts leave them out: they grow with the
    context, which no shape states."""
    d = hidden_size
    layers = n_dense_layers + n_moe_layers
    matrices = (
        layers * _attention(d, num_attention_heads, num_key_value_heads, head_dim)
        + n_dense_layers * _swiglu(d, intermediate_size)
        + n_moe_layers * (d * num_experts
                          + (num_experts_per_tok + num_shared_experts)
                          * _swiglu(d, moe_intermediate_size))
        + d * vocab_size
    )
    return 2.0 * matrices


def parameters(c: dict) -> float:
    """Every parameter the configuration's file describes, norms and the
    expert bias included."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    n = int(c["num_hidden_layers"])
    nd = int(c["num_dense_layers"])
    attention = _attention(d, int(c["num_attention_heads"]), int(c["num_key_value_heads"]), hd)
    norms = 4 * d + 2 * hd
    experts = (d * int(c["num_experts"]) + int(c["num_experts"])
               + (int(c["num_experts"]) + int(c["num_shared_experts"]))
               * _swiglu(d, int(c["moe_intermediate_size"])))
    return float(n * (attention + norms) + nd * _swiglu(d, int(c["intermediate_size"]))
                 + (n - nd) * experts + 2 * int(c["vocab_size"]) * d + d)


def decode_attention_work(c: dict, contexts: Sequence[int]) -> dict:
    """One decode step's attention over every layer, each slot in use with
    context `contexts` [slots] (positions it reads, its own included): a full
    layer reads the K/V of the whole context, a window layer of the last
    `sliding_window` positions; q.k and p.v over each query head. Returns
    {"calls": [{"flops", "bytes"}, ...]}, one a layer."""
    kd = int(c["num_key_value_heads"]) * int(c["head_dim"])
    qd = int(c["num_attention_heads"]) * int(c["head_dim"])
    window = int(c["sliding_window"])
    elem = BYTES[c["pool_dtype"]]
    calls = []
    for kind in c["layer_types"]:
        seen = [min(n, window) if kind == "sliding_attention" else n for n in contexts]
        calls.append({"flops": 2.0 * 2.0 * qd * sum(seen),
                      "bytes": elem * 2.0 * kd * sum(seen)})
    return {"calls": calls}


def decode_attention_least_time(c: dict, contexts: Sequence[int], peaks: dict) -> float:
    """Seconds one decode step's attention calls take at least."""
    return sum(rooflines.least_time(w["flops"], w["bytes"], peaks)[0]
               for w in decode_attention_work(c, contexts)["calls"])


def kv_bytes_held(c: dict, pages_full: float, pages_window: float) -> float:
    """Pool bytes of `pages_full` pages of every full layer and
    `pages_window` of every window layer, K and V."""
    kinds = list(c["layer_types"])
    page = (2 * int(c["session"]["page_size"]) * int(c["num_key_value_heads"])
            * int(c["head_dim"]) * BYTES[c["pool_dtype"]])
    return page * (pages_full * kinds.count("full_attention")
                   + pages_window * kinds.count("sliding_attention"))
