"""Operations and bytes a call NEEDS, from its shapes alone: the same
whatever implements it. Used for kernels' roofline shares and the steps'
MFU. Formulas for the training models are copied from bench.py (sound
there): ResNet-50 8.18 GFLOP forward per 224x224 image (2 x 4.09 GMAC), x3
for forward + backward; seq2seq per target token as bench.py:129-134."""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def least_time(flops: float, bytes_moved: float, peaks: dict) -> Tuple[float, str]:
    """(seconds, which bound): the larger of operations over peak FLOP/s and
    bytes over peak bytes/s."""
    t_f = flops / peaks["flops_bf16"]
    t_b = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


# -- training models ----------------------------------------------------------

RESNET50_FWD_FLOPS_224 = 8.18e9  # per image, 2 x 4.09 GMAC (He et al. table 1)


def resnet50_train_flops_per_image(image_size: int = 224) -> float:
    """Forward + backward, recomputed work not counted: 3 x forward, scaled
    by area from the 224x224 count."""
    return 3.0 * RESNET50_FWD_FLOPS_224 * (image_size / 224.0) ** 2


def seq2seq_train_flops_per_token(
    embed: int, hidden: int, trg_vocab: int, src_len: int, trg_len: int
) -> float:
    """Per TARGET token, forward + backward = 3 x forward; copied from
    bench.py:129-134 (multiply-adds x 2): the bi-GRU encoder's input and
    recurrent products (amortised over the pair's target tokens), the
    attention-GRU decoder's ([embedding, context(2H)] and recurrent), the
    attention's scores and context over the source positions, and the
    output projection, which dominates."""
    e, h = embed, hidden
    enc = 2 * 3 * (e * h + h * h) * 2 * (src_len / trg_len)
    dec = 3 * ((e + 2 * h) * h + h * h) * 2
    attn = src_len * (2 * h) * 2
    out = h * trg_vocab * 2
    return 3.0 * (enc + dec + attn + out)


# -- recurrent kernels --------------------------------------------------------

def gru_seq_work(t: int, b: int, h: int, backward: bool, dtype_bytes: int = 4) -> Dict[str, float]:
    """One fused GRU sequence call over T steps: the recurrent products
    h @ [w_z, w_r] (h x 2h) and (r*h) @ w_c (h x h) per step; the input
    projections are computed outside. Backward does the transposed products
    and the weight gradients: 2 x the forward's products. Bytes: the
    projections [B, T, 3h] read, outputs [B, T, h] written, weights once;
    backward reads them again with the incoming gradient and writes dproj."""
    mm = 2.0 * b * (h * 2 * h + h * h) * t
    act_in, act_out = b * t * 3 * h, b * t * h
    weights = 3 * h * h + 3 * h
    if not backward:
        return {"flops": mm, "bytes": dtype_bytes * (act_in + act_out + weights)}
    return {
        "flops": 3.0 * mm,  # recompute of gates + dh products + dW products
        "bytes": dtype_bytes * (2 * act_in + 3 * act_out + 2 * weights),
    }


# -- serving ------------------------------------------------------------------

def lm_params_touched_per_token(d_model: int, n_layers: int, vocab: int) -> float:
    """Matrix parameters every token multiplies with: 12 d^2 a layer (q, k,
    v, o, 4d MLP up and down) and the unembedding; the embedding and the
    position rows are looked up, not multiplied."""
    return 12.0 * d_model * d_model * n_layers + d_model * vocab


def lm_flops_per_token(d_model: int, n_layers: int, vocab: int) -> float:
    return 2.0 * lm_params_touched_per_token(d_model, n_layers, vocab)


def paged_attention_decode_work(
    context_lens: Sequence[int], n_heads: int, head_dim: int, page_size: int,
    dtype_bytes: int = 4,
) -> Dict[str, float]:
    """One layer's decode attention over the slots in use: each slot reads
    the K and V pages that hold its context (whole pages, as any paged
    implementation must) and does q.k and p.v over its own positions."""
    kd = n_heads * head_dim
    pages = sum(-(-n // page_size) for n in context_lens if n > 0)
    flops = sum(2.0 * 2.0 * n * kd for n in context_lens)
    bytes_moved = dtype_bytes * (2.0 * pages * page_size * kd + 2.0 * len(context_lens) * kd)
    return {"flops": flops, "bytes": bytes_moved}
