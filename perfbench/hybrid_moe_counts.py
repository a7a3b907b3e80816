"""Operations a token NEEDS and bytes a decode step MUST MOVE of a hybrid
Mamba-2 / attention decoder with routed experts of which one rank holds a
part, from the configuration's shapes alone: the same work whatever
implements it (readers/mfu_serve_looped.py through the configuration's
`flops` entry; readers/decode_bytes_roofline.py)."""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def _mamba_matrices(hidden_size, mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups):
    d_in = mamba_n_heads * mamba_d_head
    in_w = 2 * d_in + 2 * mamba_n_groups * mamba_d_state + mamba_n_heads   # z | xBC | dt
    return hidden_size * in_w + d_in * hidden_size


def _attention_matrices(hidden_size, num_attention_heads, num_key_value_heads, head_dim):
    return hidden_size * head_dim * (2 * num_attention_heads + 2 * num_key_value_heads)


def _expert(hidden_size, width):
    return 3 * hidden_size * width             # [a | b] in, one out


def hybrid_moe_flops_per_token(
    hidden_size, n_mamba_layers, n_attention_layers, mamba_n_heads, mamba_d_head, mamba_d_state,
    mamba_n_groups, mamba_d_conv, num_attention_heads, num_key_value_heads, head_dim,
    num_experts_routed, experts_held, num_experts_per_tok, intermediate_size,
    shared_intermediate_size, vocab_size,
) -> float:
    """2 x the matrix parameters a token multiplies with on THIS rank: both
    kinds of mixer, the router, the shared MLP, of the `num_experts_per_tok`
    chosen experts the share held here in expectation, and the head over the
    vocabulary slice held; plus the convolution (2 a tap and channel) and the
    recurrence (5 a state element: decay, input product and add, readout
    product and add). Attention's products with the context are left out, as
    mfu.serve's counts leave them out: they grow with the context, and at
    this cell's contexts are a thousandth of the rest."""
    n_layers = n_mamba_layers + n_attention_layers
    d_in = mamba_n_heads * mamba_d_head
    matrices = (
        n_mamba_layers * _mamba_matrices(hidden_size, mamba_n_heads, mamba_d_head,
                                         mamba_d_state, mamba_n_groups)
        + n_attention_layers * _attention_matrices(hidden_size, num_attention_heads,
                                                   num_key_value_heads, head_dim)
        + n_layers * (
            hidden_size * num_experts_routed
            + _expert(hidden_size, shared_intermediate_size)
            + num_experts_per_tok * experts_held / num_experts_routed
            * _expert(hidden_size, intermediate_size)
        )
        + hidden_size * vocab_size
    )
    conv = n_mamba_layers * mamba_d_conv * (d_in + 2 * mamba_n_groups * mamba_d_state)
    state = n_mamba_layers * mamba_n_heads * mamba_d_head * mamba_d_state
    return 2.0 * matrices + 2.0 * conv + 5.0 * state


def parameters_held(c: dict) -> float:
    """Every parameter this rank holds, from the configuration file's keys."""
    d = int(c["hidden_size"])
    kinds = list(c["layer_types"])
    n_mamba = kinds.count("mamba")
    h, p, n, g = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    conv_dim = h * p + 2 * g * n
    mamba = (_mamba_matrices(d, h, p, n, g) + conv_dim * (int(c["mamba_d_conv"]) + 1)
             + 3 * h + h * p)                        # conv and its bias, A, dt_bias, D, the norm
    attention = _attention_matrices(d, int(c["num_attention_heads"]),
                                    int(c["num_key_value_heads"]),
                                    d // int(c["num_attention_heads"]))
    every = (2 * d + d * int(c["num_experts_routed"])
             + len(c["experts_held"]) * _expert(d, int(c["intermediate_size"]))
             + _expert(d, int(c["shared_intermediate_size"])))
    return float(n_mamba * mamba + (len(kinds) - n_mamba) * attention + len(kinds) * every
                 + int(c["vocab_size"]) * d + d)


def state_bytes_per_slot(c: dict) -> float:
    """The recurrent state and the convolution's tail of every Mamba layer."""
    h, p, n, g = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    tail = (int(c["mamba_d_conv"]) - 1) * (h * p + 2 * g * n)
    return float(list(c["layer_types"]).count("mamba")
                 * (h * p * n * BYTES[c["state_dtype"]] + tail * BYTES[c["weights_dtype"]]))


def kv_bytes_per_token(c: dict) -> float:
    """K and V of the attention layers, the only ones that leave any."""
    kd = int(c["num_key_value_heads"]) * (int(c["hidden_size"]) // int(c["num_attention_heads"]))
    return float(2 * list(c["layer_types"]).count("attention") * kd * BYTES[c["pool_dtype"]])


def decode_step_bytes(c: dict, live_slots: float, context_tokens: float = 0.0) -> float:
    """What one decode step must move through HBM: every held parameter once,
    the state of every live slot read and written, and the K/V of the live
    contexts (`context_tokens`: their sum, page padding left out) read."""
    return (parameters_held(c) * BYTES[c["weights_dtype"]]
            + 2.0 * live_slots * state_bytes_per_slot(c)
            + context_tokens * kv_bytes_per_token(c))
