"""ResNet-50 (He et al. 2015, arXiv:1512.03385, table 1, 50-layer column):
forward pass and loss in plain jax.numpy, NHWC, float32.

Stem: 7x7/2 conv 64, batch norm, relu, 3x3/2 max pool (padding 1). Four
stages of (3, 4, 6, 3) bottleneck blocks, widths (64, 256) ... (512, 2048):
1x1 conv, 3x3 conv, 1x1 conv, each followed by batch norm, relu after the
first two; the stride (2 from stage 2 on) sits on the first 1x1 conv, as the
system under test places it (the paper's original placement; torchvision's
"v1.5" puts it on the 3x3). A 1x1 projection with batch norm on the
shortcut where the shape changes; relu after the add. Global average pool,
a 1000-way linear layer, softmax cross-entropy averaged over the batch.
Batch norm uses the batch's own biased statistics (training mode), eps 1e-5.

Parameters are a flat dict: "<block>.conv.w" [kh, kw, cin, cout],
"<block>.bn.scale", "<block>.bn.bias", "logits.w", "logits.b", with blocks
"stem" and "s<stage>b<block>.<a|b|c|proj>"."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048))
EPS = 1e-5


def _conv(x, w, stride, pad, cast):
    return lax.conv_general_dilated(
        cast(x), cast(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )


def _bn(x, scale, bias):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * scale + bias


def _conv_bn(p, name, x, k, stride, relu, cast):
    y = _conv(x, p[f"{name}.conv.w"], stride, (k - 1) // 2, cast)
    y = _bn(y, p[f"{name}.bn.scale"], p[f"{name}.bn.bias"])
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, name, x, stride, project, cast):
    y = _conv_bn(p, f"{name}.a", x, 1, stride, True, cast)
    y = _conv_bn(p, f"{name}.b", y, 3, 1, True, cast)
    y = _conv_bn(p, f"{name}.c", y, 1, 1, False, cast)
    if project:
        x = _conv_bn(p, f"{name}.proj", x, 1, stride, False, cast)
    return jax.nn.relu(y + x)


def logits(p, image, cast):
    x = _conv_bn(p, "stem", image, 7, 2, True, cast)
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )
    cin = 64
    for stage, (blocks, _mid, out) in enumerate(STAGES):
        for blk in range(blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            project = stride != 1 or cin != out
            # remat per block: the float32 activations of a 256-image batch
            # do not fit the chip otherwise; the arithmetic is unchanged
            block = jax.checkpoint(
                lambda p_, x_, n=f"s{stage}b{blk}", s=stride, pr=project:
                _bottleneck(p_, n, x_, s, pr, cast)
            )
            x = block(p, x)
            cin = out
    pooled = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(cast(pooled), cast(p["logits.w"]), precision=lax.Precision.HIGHEST) + p["logits.b"]


def make_loss(config: dict, cast):
    def loss(p, batch):
        z = logits(p, batch["image"].astype(jnp.float32), cast)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(logp, batch["label"].astype(jnp.int32)[:, None], axis=-1)
        return -jnp.mean(picked)
    return loss
