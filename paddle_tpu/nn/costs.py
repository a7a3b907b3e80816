"""Cost (loss) layers.

Parity with paddle/gserver/layers/CostLayer.cpp: multi-class cross-entropy
(+softmax fused, hl_matrix.h softmax+CE kernels), soft binary CE, squared error,
rank cost, lambda cost, huber; plus classification output. Each cost layer
outputs a per-example cost [B] (or [B,1]); the trainer averages/sums — matching
Argument::sum over the cost layer output in TrainerInternal.cpp:66."""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import LAYERS
from paddle_tpu.nn.graph import Argument, Context, Layer
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.ops import sequence as seq_ops
from paddle_tpu.ops import xent as xent_ops

Array = jax.Array


def _flatten_seq(value: Array, lengths: Optional[Array]):
    """[B,T,...]+lengths → flat [(B*T), ...] values and [(B*T)] weight mask; or
    pass-through for non-sequence [B, ...]."""
    if lengths is None:
        return value, None
    b, t = value.shape[0], value.shape[1]
    mask = seq_ops.mask_from_lengths(lengths, t).reshape(-1)
    flat = value.reshape((b * t,) + value.shape[2:])
    return flat, mask


def _masked_mean(ctx: Context, cost: Array, batch_rows: int, timesteps=None):
    """Mean over examples honoring Context.sample_mask — the [B] 0/1 row
    validity from a mesh-divisibility-padded batch (graph.SAMPLE_MASK_KEY).
    Padded rows weigh 0 and the denominator is the REAL row count, so the
    padded batch reproduces the unpadded batch's cost (and, through the
    backward, its gradients). Without a mask this is the plain sum/B the
    trainer always used — bitwise-unchanged for unpadded batches."""
    smask = getattr(ctx, "sample_mask", None)
    if smask is None:
        return jnp.sum(cost) / batch_rows
    w = smask.astype(cost.dtype).reshape(-1)
    if timesteps is not None:  # sequence costs flatten to [(B*T)]
        w = jnp.repeat(w, timesteps)
    denom = jnp.maximum(jnp.sum(smask.astype(jnp.float32)), 1.0)
    return jnp.sum(cost * w) / denom


class CostLayer(Layer):
    """Base for costs: handles sequence flattening + per-example weighting."""

    is_cost = True

    def __init__(self, input: Layer, label: Layer, weight: Optional[Layer] = None, name=None, coeff: float = 1.0):
        srcs = [input, label] + ([weight] if weight is not None else [])
        super().__init__(srcs, name=name)
        self.coeff = coeff
        self.has_weight = weight is not None

    def per_example(self, ctx, pred: Array, label: Array) -> Array:
        raise NotImplementedError

    def forward(self, ctx: Context, ins: List[Argument], projection=None) -> Argument:
        """`projection` is set by a Network that gave this cost its input
        layer's work (graph._plan_fused_projections): ins[0] is then
        that layer's INPUT, and per_example_projected applies its
        parameters. Masks, weights and the mean are the same either way."""
        pred_arg, label_arg = ins[0], ins[1]
        if pred_arg.lengths is not None and label_arg.lengths is None:
            # sequence predictions against one label per sequence: the label
            # applies to every (valid) step, as the reference's provider
            # binding does when a non-seq label slot meets a seq cost input
            t = pred_arg.value.shape[1]
            lv = label_arg.value.reshape(label_arg.value.shape[0], -1)
            label_arg = Argument(
                jnp.broadcast_to(lv[:, :1], (lv.shape[0], t)),
                pred_arg.lengths,
            )
        pred, pmask = _flatten_seq(pred_arg.value, pred_arg.lengths)
        label, _ = _flatten_seq(label_arg.value, label_arg.lengths)
        if projection is None:
            cost = self.per_example(ctx, pred, label)
        else:
            cost = self.per_example_projected(ctx, projection, pred, label)
        if cost.ndim > 1:
            cost = cost.reshape(cost.shape[0], -1).sum(-1)
        if pmask is not None:
            cost = cost * pmask
        if self.has_weight:
            w = ins[2].value.reshape(-1)
            cost = cost * w
        # mean over examples (sequences count each timestep, like the reference's
        # per-instance sum normalized by batch size in Argument::sum semantics).
        t = pred_arg.value.shape[1] if pred_arg.lengths is not None else None
        total = self.coeff * _masked_mean(
            ctx, cost, pred_arg.value.shape[0], timesteps=t
        )
        return Argument(total)


def _count_xent_path(ctx, value, path: str) -> None:
    """One count per cost each time a forward is TRACED (jit, grad,
    eval_shape), so a compiled step counts once however often it runs. Not
    in init, which runs every layer as written, and not on an eager call."""
    if ctx.mode == "apply" and isinstance(value, jax.core.Tracer):
        obs_metrics.observe_fused_projection_xent(path)


@LAYERS.register("classification_cost", "multi_class_cross_entropy")
class ClassificationCost(CostLayer):
    """Softmax + multi-class cross-entropy (CostLayer.cpp
    MultiClassCrossEntropy; the v1 helper classification_cost applies softmax
    activation on the input layer — here fused via log_softmax for stability).
    Input: logits or probabilities; set `from_logits=False` if the input layer
    already applied softmax."""

    type_name = "classification_cost"

    def __init__(self, input, label, weight=None, name=None, coeff=1.0, from_logits=True):
        super().__init__(input, label, weight, name, coeff)
        self.from_logits = from_logits

    @property
    def fuses_projection(self) -> bool:
        """Whether this cost can take a linear projection's place in front
        of it (ops/xent.linear_softmax_xent): only from logits."""
        return self.from_logits

    def per_example(self, ctx, pred, label):
        label = label.astype(jnp.int32).reshape(-1)
        if self.from_logits:
            # every [N, V] tensor stays in pred's dtype, reductions in f32
            # (ops/xent.py); pred is float32 when a biased Fc made it
            _count_xent_path(ctx, pred, "unfused")
            return xent_ops.softmax_xent_with_logits(pred, label)
        logp = jnp.log(jnp.maximum(pred.astype(jnp.float32), 1e-10))
        return -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]

    def per_example_projected(self, ctx, projection, rows, label):
        """rows [N, ...] are the projection layer's input: the logits exist
        only inside the op."""
        label = label.astype(jnp.int32).reshape(-1)
        x = rows.reshape(rows.shape[0], -1)
        w, b = projection.projection_params(ctx, x.shape[-1])
        _count_xent_path(ctx, x, "fused")
        return xent_ops.linear_softmax_xent(x, w, b, label, ctx.policy)


@LAYERS.register("soft_binary_class_cross_entropy")
class SoftBinaryCrossEntropy(CostLayer):
    """Per-dimension binary CE with soft targets (SoftBinaryClassCrossEntropy)."""

    type_name = "soft_binary_class_cross_entropy"

    def per_example(self, ctx, pred, label):
        p = jnp.clip(pred.astype(jnp.float32), 1e-7, 1 - 1e-7)
        y = label.astype(jnp.float32)
        return -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p)).sum(-1)


@LAYERS.register("square_error", "mse_cost", "regression_cost")
class SquareError(CostLayer):
    """Sum-of-squares error (SumOfSquaresCostLayer): 0.5*||pred-label||^2."""

    type_name = "square_error"

    def per_example(self, ctx, pred, label):
        d = pred.astype(jnp.float32) - _dense_label(pred, label)
        return 0.5 * jnp.sum(d * d, axis=-1)


@LAYERS.register("cross_entropy_with_selfnorm")
class CrossEntropyWithSelfNorm(CostLayer):
    """MultiClassCrossEntropyWithSelfNorm: CE + alpha * log(Z)^2 self-norm."""

    type_name = "cross_entropy_with_selfnorm"

    def __init__(self, input, label, weight=None, name=None, coeff=1.0, softmax_selfnorm_alpha=0.1):
        super().__init__(input, label, weight, name, coeff)
        self.alpha = softmax_selfnorm_alpha

    def per_example(self, ctx, pred, label):
        logits = pred.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        logp = logits - logz[:, None]
        label = label.astype(jnp.int32).reshape(-1)
        ce = -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
        return ce + self.alpha * logz * logz


def _dense_label(pred, label):
    """Regression costs against an id label slot (the provider binds whatever
    the cost consumes; one-hot is the dense view of ids)."""
    if label.ndim == pred.ndim - 1:
        return jax.nn.one_hot(label.astype(jnp.int32), pred.shape[-1])
    return label.astype(jnp.float32)


@LAYERS.register("huber_regression_cost")
class HuberRegression(CostLayer):
    """HuberRegressionLoss (CostLayer.cpp)."""

    type_name = "huber_regression_cost"

    def __init__(self, input, label, weight=None, name=None, coeff=1.0, delta=1.0):
        super().__init__(input, label, weight, name, coeff)
        self.delta = delta

    def per_example(self, ctx, pred, label):
        d = jnp.abs(pred.astype(jnp.float32) - _dense_label(pred, label))
        quad = jnp.minimum(d, self.delta)
        return jnp.sum(0.5 * quad * quad + self.delta * (d - quad), axis=-1)


@LAYERS.register("huber_classification_cost")
class HuberTwoClassification(CostLayer):
    """HuberTwoClassification (labels {0,1} → y∈{-1,1}, squared hinge-ish)."""

    type_name = "huber_classification_cost"

    def per_example(self, ctx, pred, label):
        y = 2.0 * label.astype(jnp.float32).reshape(-1) - 1.0
        z = pred.astype(jnp.float32).reshape(-1) * y
        return jnp.where(z < -1, -4 * z, jnp.where(z < 1, jnp.square(1 - z), 0.0))


@LAYERS.register("rank_cost")
class RankCost(Layer):
    """Pairwise ranking cost (RankingCost, CostLayer.cpp): inputs left/right
    scores + label in [0,1] preference."""

    type_name = "rank_cost"
    is_cost = True

    def __init__(self, left: Layer, right: Layer, label: Layer, weight=None, name=None, coeff=1.0):
        srcs = [left, right, label] + ([weight] if weight is not None else [])
        super().__init__(srcs, name=name)
        self.coeff = coeff
        self.has_weight = weight is not None

    def forward(self, ctx, ins):
        o = (ins[0].value - ins[1].value).astype(jnp.float32).reshape(-1)
        t = ins[2].value.astype(jnp.float32).reshape(-1)
        cost = jax.nn.softplus(o) - t * o  # log(1+e^o) - t*o
        if self.has_weight:
            cost = cost * ins[3].value.reshape(-1)
        return Argument(self.coeff * _masked_mean(ctx, cost, cost.shape[0]))


@LAYERS.register("multi_binary_label_cross_entropy")
class MultiBinaryLabelCrossEntropy(CostLayer):
    """MultiBinaryLabelCrossEntropy: sigmoid CE against multi-hot labels."""

    type_name = "multi_binary_label_cross_entropy"

    def per_example(self, ctx, pred, label):
        x = pred.astype(jnp.float32)
        y = _dense_label(pred, label)
        # stable sigmoid CE on logits
        return jnp.sum(jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))), axis=-1)


@LAYERS.register("sum_cost")
class SumCost(Layer):
    is_cost = True
    """SumCostLayer: cost = sum of input activations."""

    type_name = "sum_cost"

    def __init__(self, input: Layer, name=None, coeff: float = 1.0):
        super().__init__(input, name=name)
        self.coeff = coeff

    def forward(self, ctx, ins):
        v = ins[0].value
        if getattr(ctx, "sample_mask", None) is None:
            return Argument(self.coeff * jnp.sum(v) / v.shape[0])
        per_row = jnp.sum(v.reshape(v.shape[0], -1), axis=-1)
        return Argument(self.coeff * _masked_mean(ctx, per_row, v.shape[0]))


@LAYERS.register("smooth_l1_cost")
class SmoothL1(CostLayer):
    """SmoothL1CostLayer."""

    type_name = "smooth_l1_cost"

    def per_example(self, ctx, pred, label):
        d = jnp.abs(pred.astype(jnp.float32) - _dense_label(pred, label))
        return jnp.sum(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5), axis=-1)
