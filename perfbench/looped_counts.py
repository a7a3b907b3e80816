"""Operations a token NEEDS of a looped decoder, from the configuration's
shapes alone (perfbench/readers/mfu_serve_looped.py; the configuration's
`flops` entry names the function, as readers/mfu_train.py has it)."""

from __future__ import annotations


def looped_lm_params_touched_per_token(
    hidden_size: int, num_attention_heads: int, head_dim: int, intermediate_size: int,
    num_hidden_layers: int, total_ut_steps: int, vocab_size: int,
) -> float:
    """Matrix parameters every token multiplies with: a layer's q, k, v and
    o (4 d kd) and its gated MLP's three (3 d f), once a PASS over every
    layer, and the unembedding once; the embedding's row is looked up."""
    d, kd, f = hidden_size, num_attention_heads * head_dim, intermediate_size
    return float(total_ut_steps * num_hidden_layers * (4 * d * kd + 3 * d * f) + d * vocab_size)


def looped_lm_flops_per_token(**shapes) -> float:
    return 2.0 * looped_lm_params_touched_per_token(**shapes)
