"""Core layers (batch 1: dense / image / elementwise).

TPU-native re-implementations of the reference layer types in
paddle/gserver/layers/ (93 REGISTER_LAYER registrations, Layer.h:31). Each class
docstring cites the reference layer it matches. Layers are pure specs — see
paddle_tpu/nn/graph.py; backward is autodiff."""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.registry import LAYERS
from paddle_tpu.nn import activations as act_mod
from paddle_tpu.nn import init as init_mod
from paddle_tpu.nn.graph import Argument, Context, Layer, ParamAttr
from paddle_tpu.ops import conv as conv_ops
from paddle_tpu.ops import linalg
from paddle_tpu.ops import normalization as norm_ops

Array = jax.Array


def _attr(a: Optional[Union[ParamAttr, dict]]) -> Optional[ParamAttr]:
    if a is None or isinstance(a, ParamAttr):
        return a
    if isinstance(a, bool):  # bias_attr=True/False toggles, carries no attrs
        return None
    if isinstance(a, (list, tuple)):  # per-input attrs (multi-input fc/mixed)
        return [_attr(x) for x in a]
    return ParamAttr(**a)


@LAYERS.register("data")
class Data(Layer):
    """Input slot (DataLayer, gserver/layers/DataLayer.cpp). `shape` excludes the
    batch dim; sequence inputs additionally carry lengths in the feed dict."""

    type_name = "data"

    def __init__(self, name: str, shape: Sequence[int] = (), is_seq: bool = False):
        super().__init__(None, name=name)
        self.shape = tuple(shape)
        self.is_seq = is_seq

    def forward(self, ctx, ins):  # data layers are fed directly by Network._run
        raise AssertionError("data layer forward should not be called")


@LAYERS.register("fc")
class Fc(Layer):
    """Fully-connected (FullyConnectedLayer.cpp). Multiple inputs each get their
    own weight, summed before bias+activation — matching the reference, whose fc
    accepts several inputs. Sequence inputs are applied per-timestep."""

    type_name = "fc"

    def __init__(
        self,
        input: Union[Layer, Sequence[Layer]],
        size: int,
        act: Any = "tanh",
        bias: bool = True,
        param_attr: Any = None,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.size = size
        self.act = act
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def _weight(self, ctx: Context, i: int, d: int) -> Array:
        suffix = "" if len(self.inputs) == 1 else f".{i}"
        pa = self.param_attr
        if isinstance(pa, list):
            pa = pa[i] if i < len(pa) else None
        return ctx.param(
            self, "w" + suffix, (d, self.size), init_mod.smart_normal, pa
        )

    def _bias(self, ctx: Context) -> Optional[Array]:
        if not self.bias:
            return None
        return ctx.param(self, "b", (self.size,), init_mod.zeros, self.bias_attr)

    @property
    def is_linear_projection(self) -> bool:
        """One input, no activation: x @ w + b and nothing else, which a
        cost that fuses its projection (ops/xent.linear_softmax_xent) can
        take over. Whether it does is the Network's decision."""
        return len(self.inputs) == 1 and act_mod.get(self.act) is act_mod.linear

    def projection_params(self, ctx: Context, d: int):
        """(w, b) of a linear projection from width d, under the parameters'
        own names, for the cost that does this layer's work."""
        return self._weight(ctx, 0, d), self._bias(ctx)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        total = None
        any_seq = any(a.is_seq for a in ins)
        for i, arg in enumerate(ins):
            x = arg.value
            if not arg.is_seq and x.ndim > 2:
                # image/feature-map input: v1 fc operates on the flattened
                # vector (FullyConnectedLayer consumes the flat Argument)
                x = x.reshape(x.shape[0], -1)
            w = self._weight(ctx, i, x.shape[-1])
            y = linalg.matmul(x, w, ctx.policy)
            if any_seq and y.ndim == 2:
                # flat input mixed with sequence inputs: broadcast over time
                # (the reference adds the non-seq row to every token)
                y = y[:, None]
            total = y if total is None else total + y
        b = self._bias(ctx)
        if b is not None:
            total = total + b
        total = act_mod.apply(self.act, total)
        return ins[0].with_value(total)


@LAYERS.register("embedding")
class Embedding(Layer):
    """Embedding lookup (TableProjection + hl_table_apply row select,
    paddle/cuda/src/hl_table_apply.cu). Input carries int ids [B] or [B, T]."""

    type_name = "embedding"

    def __init__(
        self,
        input: Layer,
        size: int,
        vocab_size: Optional[int] = None,
        param_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.size = size
        self.vocab_size = vocab_size
        self.param_attr = _attr(param_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        ids = ins[0].value
        vocab = self.vocab_size
        if vocab is None:
            src = self.inputs[0]
            vocab = getattr(src, "shape", (None,))[0]
            if vocab is None:
                raise ValueError(
                    f"embedding {self.name}: vocab_size not set and input has no shape"
                )
        table = ctx.param(
            self, "w", (vocab, self.size), init_mod.smart_normal, self.param_attr
        )
        out = jnp.take(table, ids.astype(jnp.int32), axis=0)
        return ins[0].with_value(out)


@LAYERS.register("conv")
class Conv2D(Layer):
    """2-D convolution, NHWC (ExpandConvLayer.cpp / CudnnConvBaseLayer.cpp via
    GemmConvOp; here a single XLA conv HLO on the MXU)."""

    type_name = "conv"

    def __init__(
        self,
        input: Layer,
        num_filters: int,
        filter_size: Union[int, Tuple[int, int]],
        stride: Union[int, Tuple[int, int]] = 1,
        padding: Union[int, Tuple[int, int], str] = 0,
        dilation: Union[int, Tuple[int, int]] = 1,
        groups: int = 1,
        act: Any = None,
        bias: bool = True,
        param_attr: Any = None,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.num_filters = num_filters
        self.filter_size = filter_size
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.act = act
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        assert x.ndim == 4, f"conv {self.name}: expect NHWC input, got {x.shape}"
        kh, kw = conv_ops._pair(self.filter_size)
        cin = x.shape[-1]
        w = ctx.param(
            self,
            "w",
            (kh, kw, cin // self.groups, self.num_filters),
            init_mod.he_normal,
            self.param_attr,
        )
        out = conv_ops.conv2d(
            x, w, self.stride, self.padding, self.dilation, self.groups, ctx.policy
        )
        if self.bias:
            b = ctx.param(self, "b", (self.num_filters,), init_mod.zeros, self.bias_attr)
            out = out + b
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("conv_transpose")
class Conv2DTranspose(Layer):
    """Transposed 2-D conv (ExpandConvLayer with trans=True; ConvTransLayerBase)."""

    type_name = "conv_transpose"

    def __init__(
        self,
        input: Layer,
        num_filters: int,
        filter_size: Union[int, Tuple[int, int]],
        stride: Union[int, Tuple[int, int]] = 1,
        padding: Union[int, Tuple[int, int]] = 0,
        act: Any = None,
        bias: bool = True,
        param_attr: Any = None,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.num_filters = num_filters
        self.filter_size = filter_size
        self.stride = stride
        self.padding = padding
        self.act = act
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        kh, kw = conv_ops._pair(self.filter_size)
        cin = x.shape[-1]
        w = ctx.param(
            self,
            "w",
            (kh, kw, self.num_filters, cin),
            init_mod.he_normal,
            self.param_attr,
        )
        out = conv_ops.conv2d_transpose(x, w, self.stride, self.padding, ctx.policy)
        if self.bias:
            b = ctx.param(self, "b", (self.num_filters,), init_mod.zeros, self.bias_attr)
            out = out + b
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("pool")
class Pool2D(Layer):
    """Max/avg pooling, NHWC (PoolLayer.cpp / CudnnPoolLayer.cpp;
    hl_maxpool/avgpool kernels in hl_cuda_cnn.cu)."""

    type_name = "pool"

    def __init__(
        self,
        input: Layer,
        pool_size: Union[int, Tuple[int, int]],
        pool_type: str = "max",
        stride: Optional[Union[int, Tuple[int, int]]] = None,
        padding: Union[int, Tuple[int, int]] = 0,
        ceil_mode: bool = False,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        assert pool_type in ("max", "avg")
        self.pool_size = pool_size
        self.pool_type = pool_type
        self.stride = stride
        self.padding = padding
        self.ceil_mode = ceil_mode

    def _pads(self, x) -> Any:
        """ceil_mode=True (the v1 default, MathUtils outputSize with
        caffeMode=false): out = ceil((I + 2p - f) / s) + 1. Emulated with
        extra bottom/right padding so partial windows at the edge survive."""
        if not self.ceil_mode:
            return self.padding
        fh, fw = conv_ops._pair(self.pool_size)
        sh, sw = conv_ops._pair(
            self.stride if self.stride is not None else self.pool_size
        )
        ph, pw = conv_ops._pair(self.padding)
        out = []
        for size, f, s, p in ((x.shape[1], fh, sh, ph), (x.shape[2], fw, sw, pw)):
            n_out = -(-(size + 2 * p - f) // s) + 1  # ceil-div
            extra = max(0, (n_out - 1) * s + f - size - 2 * p)
            out.append((p, p + extra))
        return tuple(out)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        pads = self._pads(x)
        if self.pool_type == "max":
            out = conv_ops.max_pool2d(x, self.pool_size, self.stride, pads)
        else:
            out = conv_ops.avg_pool2d(x, self.pool_size, self.stride, pads)
        return ins[0].with_value(out)


@LAYERS.register("batch_norm")
class BatchNorm(Layer):
    """Batch normalization (BatchNormalizationLayer.cpp / CudnnBatchNormLayer.cpp;
    hl_batch_norm.cu). Works on [B, D] or NHWC [B, H, W, C]; moving stats are
    functional state updated only in train mode (movingAvgFraction default 0.9,
    BatchNormBaseLayer)."""

    type_name = "batch_norm"

    def __init__(
        self,
        input: Layer,
        act: Any = None,
        epsilon: float = 1e-5,
        moving_average_fraction: float = 0.9,
        use_global_stats: Optional[bool] = None,
        param_attr: Any = None,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.act = act
        self.epsilon = epsilon
        self.maf = moving_average_fraction
        self.use_global_stats = use_global_stats
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        c = x.shape[-1]
        gamma = ctx.param(self, "scale", (c,), init_mod.ones, self.param_attr)
        beta = ctx.param(self, "bias", (c,), init_mod.zeros, self.bias_attr)
        moving_mean = ctx.state(self, "moving_mean", (c,), 0.0)
        moving_var = ctx.state(self, "moving_var", (c,), 1.0)
        use_global = (
            self.use_global_stats
            if self.use_global_stats is not None
            else not ctx.train
        )
        if use_global:
            out = norm_ops.batch_norm_inference(
                x, gamma, beta, moving_mean, moving_var, self.epsilon
            )
        else:
            # fused one-pass stats + minimal-pass custom VJP — the profiled
            # bandwidth hot spot of conv/BN models (ops/normalization.py)
            out, mean, var = norm_ops.batch_norm_train(
                x, gamma, beta, self.epsilon
            )
            ctx.update_state(
                self, "moving_mean", self.maf * moving_mean + (1 - self.maf) * mean
            )
            ctx.update_state(
                self, "moving_var", self.maf * moving_var + (1 - self.maf) * var
            )
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("dropout")
class Dropout(Layer):
    """Dropout (Layer.h drop_rate handling in Layer::forwardDropOut). Inverted
    dropout: scales by 1/(1-rate) at train time, identity at inference."""

    type_name = "dropout"

    def __init__(self, input: Layer, rate: float, name: Optional[str] = None):
        super().__init__(input, name=name)
        self.rate = rate

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        if not ctx.train or self.rate <= 0.0:
            return ins[0]
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(ctx.next_rng(self.name), keep, x.shape)
        return ins[0].with_value(jnp.where(mask, x / keep, 0).astype(x.dtype))


@LAYERS.register("addto")
class Addto(Layer):
    """Elementwise sum of N inputs (+bias, activation) — AddtoLayer.cpp.
    This is the residual-connection workhorse for ResNet."""

    type_name = "addto"

    def __init__(
        self,
        input: Sequence[Layer],
        act: Any = None,
        bias: bool = False,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        super().__init__(input, name=name)
        self.act = act
        self.bias = bias
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        out = ins[0].value
        for other in ins[1:]:
            out = out + other.value
        if self.bias:
            b = ctx.param(self, "b", (out.shape[-1],), init_mod.zeros, self.bias_attr)
            out = out + b
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("concat")
class Concat(Layer):
    """Feature-axis concat of N inputs (ConcatenateLayer.cpp)."""

    type_name = "concat"

    def __init__(self, input: Sequence[Layer], act: Any = None, name=None):
        super().__init__(input, name=name)
        self.act = act

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        out = jnp.concatenate([a.value for a in ins], axis=-1)
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("scaling")
class Scaling(Layer):
    """Row-wise scale: out[i] = w[i] * x[i], weight from first input
    (ScalingLayer.cpp: input[0]=weight [B,1], input[1]=data)."""

    type_name = "scaling"

    def __init__(self, weight: Layer, input: Layer, name=None):
        super().__init__([weight, input], name=name)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        w, x = ins[0].value, ins[1].value
        while w.ndim < x.ndim:
            w = w[..., None]
        return ins[1].with_value(w * x)


@LAYERS.register("slope_intercept")
class SlopeIntercept(Layer):
    """y = slope * x + intercept (SlopeInterceptLayer.cpp)."""

    type_name = "slope_intercept"

    def __init__(self, input: Layer, slope: float = 1.0, intercept: float = 0.0, name=None):
        super().__init__(input, name=name)
        self.slope = slope
        self.intercept = intercept

    def forward(self, ctx, ins):
        return ins[0].with_value(self.slope * ins[0].value + self.intercept)


@LAYERS.register("interpolation")
class Interpolation(Layer):
    """out = w*x + (1-w)*y with per-row weight (InterpolationLayer.cpp).
    inputs: [weight [B,1], x, y]."""

    type_name = "interpolation"

    def __init__(self, weight: Layer, input1: Layer, input2: Layer, name=None):
        super().__init__([weight, input1, input2], name=name)

    def forward(self, ctx, ins):
        w = ins[0].value
        x, y = ins[1].value, ins[2].value
        while w.ndim < x.ndim:
            w = w[..., None]
        return ins[1].with_value(w * x + (1.0 - w) * y)


@LAYERS.register("power")
class Power(Layer):
    """out[i] = x[i] ** p[i], per-row exponent from first input (PowerLayer.cpp)."""

    type_name = "power"

    def __init__(self, exponent: Layer, input: Layer, name=None):
        super().__init__([exponent, input], name=name)

    def forward(self, ctx, ins):
        p, x = ins[0].value, ins[1].value
        while p.ndim < x.ndim:
            p = p[..., None]
        return ins[1].with_value(jnp.power(x, p))


@LAYERS.register("dot_prod")
class DotProd(Layer):
    """Row-wise dot product of two inputs → [B, 1] (DotProdLayer.cpp)."""

    type_name = "dot_prod"

    def __init__(self, input1: Layer, input2: Layer, name=None):
        super().__init__([input1, input2], name=name)

    def forward(self, ctx, ins):
        out = jnp.sum(ins[0].value * ins[1].value, axis=-1, keepdims=True)
        return ins[0].with_value(out)


@LAYERS.register("cos_sim")
class CosSim(Layer):
    """Row-wise cosine similarity ×scale → [B, 1] (CosSimLayer.cpp,
    paddle/function/CosSimOp.cpp)."""

    type_name = "cos_sim"

    def __init__(self, input1: Layer, input2: Layer, scale: float = 1.0, name=None):
        super().__init__([input1, input2], name=name)
        self.scale = scale

    def forward(self, ctx, ins):
        a, b = ins[0].value, ins[1].value
        num = jnp.sum(a * b, axis=-1, keepdims=True)
        den = jnp.linalg.norm(a, axis=-1, keepdims=True) * jnp.linalg.norm(
            b, axis=-1, keepdims=True
        )
        return ins[0].with_value(self.scale * num / jnp.maximum(den, 1e-12))


@LAYERS.register("convex_comb")
class LinearComb(Layer):
    """Per-sample weighted sum of vectors (ConvexCombinationLayer /
    linear_comb_layer, layers.py:4984): weights [B, M], vectors [B, M*N] →
    z[i] = Σ_j x[j]·y[i+N·j], i.e. z = xᵀ·Y with Y = vectors.reshape(M, N)."""

    type_name = "convex_comb"

    def __init__(self, weights: Layer, vectors: Layer, size: Optional[int] = None,
                 name: Optional[str] = None):
        super().__init__([weights, vectors], name=name)
        self.size = size

    def forward(self, ctx, ins):
        x, y = ins[0].value, ins[1].value
        b, m = x.shape
        n = self.size or y.shape[-1] // m
        assert m * n == y.shape[-1], (
            f"convex_comb {self.name}: vectors dim {y.shape[-1]} != "
            f"weights dim {m} × size {n}"
        )
        out = jnp.einsum("bm,bmn->bn", x, y.reshape(b, m, n))
        return ins[1].with_value(out)


@LAYERS.register("cos_vm")
class CosSimVecMat(Layer):
    """Cosine similarity of one vector against each row of a per-sample
    matrix (CosSimVecMatLayer.cpp): vec [B, M], mat [B, M*N] → [B, N],
    out[i] = scale · cos(vec, mat_row_i)."""

    type_name = "cos_vm"

    def __init__(self, vec: Layer, mat: Layer, size: Optional[int] = None,
                 scale: float = 1.0, name: Optional[str] = None):
        super().__init__([vec, mat], name=name)
        self.size = size
        self.scale = scale

    def forward(self, ctx, ins):
        v, m_flat = ins[0].value, ins[1].value
        b, dim = v.shape
        n = self.size or m_flat.shape[-1] // dim
        assert dim * n == m_flat.shape[-1], (
            f"cos_vm {self.name}: mat dim {m_flat.shape[-1]} != "
            f"vec dim {dim} × keys {n}"
        )
        mat = m_flat.reshape(b, n, dim)
        num = jnp.einsum("bd,bnd->bn", v, mat)
        den = jnp.linalg.norm(v, axis=-1, keepdims=True) * jnp.linalg.norm(
            mat, axis=-1
        )
        return ins[1].with_value(self.scale * num / jnp.maximum(den, 1e-12))


@LAYERS.register("mixed")
class Mixed(Layer):
    """Sum of projections (MixedLayer.cpp): each input arrives via a Projection
    object (see paddle_tpu/nn/projections.py); results are summed, then
    bias+activation — matching Projection.h/Operator.h semantics."""

    type_name = "mixed"

    def __init__(
        self,
        input: Sequence["Projection"],
        size: Optional[int] = None,
        act: Any = None,
        bias: bool = False,
        bias_attr: Any = None,
        name: Optional[str] = None,
    ):
        from paddle_tpu.nn.projections import Projection

        self.projections = []
        for p in input:
            if not isinstance(p, Projection):
                raise TypeError("mixed layer inputs must be Projections")
            self.projections.append(p)
        super().__init__([], name=name)
        self._relayout()
        self.size = size
        self.act = act
        self.bias = bias
        self.bias_attr = _attr(bias_attr)

    def _relayout(self):
        """Input-slot layout matching the reference's MixedLayer config:
        each projection/operator claims one slot in declaration order for its
        FIRST source; operators' extra sources append at the end (that is how
        the golden protostrs index operator_confs.input_indices)."""
        slots: List[Layer] = []
        arg_slots: List[List[int]] = []
        for p in self.projections:
            arg_slots.append([len(slots)])
            slots.append(p.sources[0])
        for i, p in enumerate(self.projections):
            for extra in p.sources[1:]:
                arg_slots[i].append(len(slots))
                slots.append(extra)
        self.inputs = slots
        self._arg_slots = arg_slots

    # -- incremental construction (trainer_config_helpers MixedLayerType:
    #    `with mixed_layer(size=N) as m: m += full_matrix_projection(x)`) ----
    def __iadd__(self, proj):
        from paddle_tpu.nn.projections import Projection

        if not isinstance(proj, Projection):
            raise TypeError("mixed layer inputs must be Projections")
        self.projections.append(proj)
        self._relayout()
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and not self.projections:
            raise ValueError(f"mixed layer {self.name!r} finalized with no projections")
        return False

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        out = None
        first_arg = None
        for proj, slots in zip(self.projections, self._arg_slots):
            args = [ins[j] for j in slots]
            if first_arg is None:
                first_arg = args[0]
            y = proj.apply(ctx, self, args, self.size)
            out = y if out is None else out + y
        if self.bias:
            b = ctx.param(self, "b", (out.shape[-1],), init_mod.zeros, self.bias_attr)
            out = out + b
        out = act_mod.apply(self.act, out)
        return first_arg.with_value(out)


@LAYERS.register("concat2")
class Concat2(Layer):
    """ConcatenateLayer2: apply a projection per input, concatenate results
    feature-wise (the projection-input form of concat_layer)."""

    type_name = "concat2"

    def __init__(self, input, act: Any = None, bias: bool = False,
                 bias_attr: Any = None, name: Optional[str] = None):
        from paddle_tpu.nn.projections import Projection

        self.projections = []
        srcs: List[Layer] = []
        for p in input:
            if not isinstance(p, Projection):
                raise TypeError("concat2 inputs must be Projections")
            self.projections.append(p)
            srcs.extend(p.sources)
        super().__init__(srcs, name=name)
        self.act = act
        self.bias = bias
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx, ins):
        outs = []
        pos = 0
        first_arg = None
        for proj in self.projections:
            n = len(proj.sources)
            args = ins[pos : pos + n]
            pos += n
            if first_arg is None:
                first_arg = args[0]
            outs.append(proj.apply(ctx, self, args, None))
        out = jnp.concatenate(outs, axis=-1)
        if self.bias:
            b = ctx.param(self, "b", (out.shape[-1],), init_mod.zeros, self.bias_attr)
            out = out + b
        return first_arg.with_value(act_mod.apply(self.act, out))


@LAYERS.register("trans")
class Trans(Layer):
    """TransLayer. With `height` set: transpose of the feature block
    [B, M*N] viewed as MxN. Without height: the reference transposes the
    whole batch matrix [B, D] → [D, B] (TransLayer.cpp) — shape inference
    keeps size D like the reference config parser does (a real transpose
    only round-trips when batch == D, the reference's implicit contract), so
    tracing treats it as identity and the runtime transposes."""

    type_name = "trans"

    def __init__(self, input: Layer, height: Optional[int] = None, name=None):
        super().__init__(input, name=name)
        self.height = height

    def forward(self, ctx, ins):
        x = ins[0].value
        if self.height is None:
            if ctx.mode == "init":
                return ins[0]  # config-level identity (size preserved)
            return Argument(x.T)
        b, d = x.shape
        h = self.height
        out = x.reshape(b, h, d // h).swapaxes(1, 2).reshape(b, d)
        return ins[0].with_value(out)


@LAYERS.register("reshape")
class Reshape(Layer):
    """Feature reshape (ResizeLayer semantics: reinterpret [B, D] as [B', D'])."""

    type_name = "reshape"

    def __init__(self, input: Layer, shape: Sequence[int], name=None):
        super().__init__(input, name=name)
        self.shape = tuple(shape)

    def forward(self, ctx, ins):
        x = ins[0].value
        shape = self.shape
        if -1 in shape:
            known = 1
            for d in shape:
                if d != -1:
                    known *= d
            rest = int(np.prod(x.shape[1:])) // known
            shape = tuple(rest if d == -1 else d for d in shape)
        return Argument(x.reshape((x.shape[0],) + shape))


@LAYERS.register("global_pool")
class GlobalPool(Layer):
    """Global spatial pooling NHWC → [B, C] (the reference expresses this as a
    PoolLayer with full-image kernel, e.g. resnet's pool7x7 avg)."""

    type_name = "global_pool"

    def __init__(self, input: Layer, pool_type: str = "avg", name=None):
        super().__init__(input, name=name)
        assert pool_type in ("avg", "max")
        self.pool_type = pool_type

    def forward(self, ctx, ins):
        x = ins[0].value
        if self.pool_type == "avg":
            return ins[0].with_value(jnp.mean(x, axis=(1, 2)))
        return ins[0].with_value(jnp.max(x, axis=(1, 2)))


@LAYERS.register("maxout")
class Maxout(Layer):
    """Maxout over channel groups (MaxOutLayer.cpp; hl_maxout_forward)."""

    type_name = "maxout"

    def __init__(self, input: Layer, groups: int, name=None):
        super().__init__(input, name=name)
        self.groups = groups

    def forward(self, ctx, ins):
        x = ins[0].value
        c = x.shape[-1]
        out = x.reshape(x.shape[:-1] + (c // self.groups, self.groups)).max(-1)
        return ins[0].with_value(out)


@LAYERS.register("spp")
class SpatialPyramidPool(Layer):
    """Spatial pyramid pooling (SpatialPyramidPoolLayer.cpp): concat of
    max/avg pools at pyramid levels 1,2,4,... bins → fixed-size vector."""

    type_name = "spp"

    def __init__(self, input: Layer, pyramid_height: int = 3, pool_type: str = "max", name=None):
        super().__init__(input, name=name)
        self.pyramid_height = pyramid_height
        self.pool_type = pool_type

    def forward(self, ctx, ins):
        x = ins[0].value
        b, h, w, c = x.shape
        outs = []
        for level in range(self.pyramid_height):
            bins = 2**level
            if bins > h or bins > w:
                # finer than the feature map — skip the level (input smaller
                # than the pyramid base)
                continue
            bh, bw = h // bins, w // bins
            cropped = x[:, : bh * bins, : bw * bins, :]
            tiles = cropped.reshape(b, bins, bh, bins, bw, c)
            if self.pool_type == "max":
                pooled = tiles.max(axis=(2, 4))
            else:
                pooled = tiles.mean(axis=(2, 4))
            outs.append(pooled.reshape(b, bins * bins * c))
        return Argument(jnp.concatenate(outs, axis=-1))


@LAYERS.register("lrn", "img_cmrnorm")
class CrossMapNorm(Layer):
    """Local response normalization across channels (NormProjectionLayer /
    CrossMapNormalOp, paddle/function/CrossMapNormalOp.cpp)."""

    type_name = "lrn"

    def __init__(self, input: Layer, size: int = 5, scale: float = 1e-4, power: float = 0.75, name=None):
        super().__init__(input, name=name)
        self.size = size
        self.scale = scale
        self.power = power

    def forward(self, ctx, ins):
        x = ins[0].value
        sq = jnp.square(x)
        half = self.size // 2
        # sum over a window of channels via padding + stacked slices
        padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
        c = x.shape[-1]
        acc = sum(padded[..., i : i + c] for i in range(self.size))
        denom = jnp.power(1.0 + self.scale * acc, self.power)
        return ins[0].with_value(x / denom)


@LAYERS.register("row_l2_norm")
class RowL2Norm(Layer):
    """Row-wise L2 normalization (RowL2NormLayer.cpp)."""

    type_name = "row_l2_norm"

    def forward(self, ctx, ins):
        x = ins[0].value
        return ins[0].with_value(x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12))


@LAYERS.register("cross_channel_norm")
class CrossChannelNorm(Layer):
    """Per-pixel channel L2 norm with learned per-channel scale
    (CrossChannelNormLayer.cpp, used by SSD)."""

    type_name = "cross_channel_norm"

    def forward(self, ctx, ins):
        x = ins[0].value
        c = x.shape[-1]
        scale = ctx.param(self, "scale", (c,), init_mod.ones, None)
        norm = jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        return ins[0].with_value(x / norm * scale)


@LAYERS.register("data_norm")
class DataNorm(Layer):
    """Feature standardization with precomputed stats (DataNormLayer.cpp):
    z-score / min-max / decimal-scaling using static (non-trained) stats."""

    type_name = "data_norm"

    def __init__(self, input: Layer, strategy: str = "z-score", name=None):
        super().__init__(input, name=name)
        assert strategy in ("z-score", "min-max", "decimal-scaling")
        self.strategy = strategy

    def forward(self, ctx, ins):
        x = ins[0].value
        d = x.shape[-1]
        if self.strategy == "z-score":
            mean = ctx.state(self, "mean", (d,), 0.0)
            std = ctx.state(self, "std", (d,), 1.0)
            return ins[0].with_value((x - mean) / jnp.maximum(std, 1e-12))
        if self.strategy == "min-max":
            mn = ctx.state(self, "min", (d,), 0.0)
            mx = ctx.state(self, "max", (d,), 1.0)
            return ins[0].with_value((x - mn) / jnp.maximum(mx - mn, 1e-12))
        scale = ctx.state(self, "scale", (d,), 1.0)
        return ins[0].with_value(x / jnp.maximum(scale, 1e-12))


@LAYERS.register("bilinear_interp")
class BilinearInterp(Layer):
    """Bilinear upsampling (BilinearInterpLayer.cpp; hl_bilinear_forward)."""

    type_name = "bilinear_interp"

    def __init__(self, input: Layer, out_size: Tuple[int, int], name=None):
        super().__init__(input, name=name)
        self.out_size = out_size

    def forward(self, ctx, ins):
        from paddle_tpu.ops import conv as conv_ops

        out = conv_ops.bilinear_resize(ins[0].value, *self.out_size)
        return ins[0].with_value(out)


@LAYERS.register("pad")
class Pad(Layer):
    """Zero-padding on H/W/C axes (PadLayer.cpp, paddle/function/PadOp.cpp)."""

    type_name = "pad"

    def __init__(self, input: Layer, pad_h=(0, 0), pad_w=(0, 0), pad_c=(0, 0), name=None):
        super().__init__(input, name=name)
        self.pads = (tuple(pad_h), tuple(pad_w), tuple(pad_c))

    def forward(self, ctx, ins):
        x = ins[0].value
        ph, pw, pc = self.pads
        return ins[0].with_value(jnp.pad(x, ((0, 0), ph, pw, pc)))


@LAYERS.register("crop")
class Crop(Layer):
    """Spatial crop (CropLayer.cpp, paddle/function/CropOp.cpp)."""

    type_name = "crop"

    def __init__(self, input: Layer, offset_h: int, offset_w: int, out_h: int, out_w: int, name=None):
        super().__init__(input, name=name)
        self.offset = (offset_h, offset_w)
        self.out = (out_h, out_w)

    def forward(self, ctx, ins):
        x = ins[0].value
        oh, ow = self.offset
        h, w = self.out
        return ins[0].with_value(x[:, oh : oh + h, ow : ow + w, :])


@LAYERS.register("rotate")
class Rotate(Layer):
    """90° CCW rotation of the spatial block (RotateLayer.cpp)."""

    type_name = "rotate"

    def forward(self, ctx, ins):
        return ins[0].with_value(jnp.rot90(ins[0].value, k=1, axes=(1, 2)))


@LAYERS.register("switch_order")
class SwitchOrder(Layer):
    """NHWC ↔ NCHW reorder (SwitchOrderLayer.cpp, function/SwitchOp.cpp).
    Kept for config parity; internally everything is NHWC."""

    type_name = "switch_order"

    def __init__(self, input: Layer, to: str = "NCHW", name=None):
        super().__init__(input, name=name)
        assert to in ("NCHW", "NHWC", "NCDHW", "NDHWC")
        self.to = to

    def forward(self, ctx, ins):
        x = ins[0].value
        perm = {
            "NCHW": (0, 3, 1, 2),
            "NHWC": (0, 2, 3, 1),
            "NCDHW": (0, 4, 1, 2, 3),
            "NDHWC": (0, 2, 3, 4, 1),
        }[self.to]
        return ins[0].with_value(jnp.transpose(x, perm))


@LAYERS.register("feature_map_expand")
class FeatureMapExpand(Layer):
    """Tile a [B, D] vector across feature-map positions
    (FeatureMapExpandLayer.cpp). as_row_vector=True tiles whole rows
    [a b c a b c]; False repeats each element [a a b b c c]."""

    type_name = "feature_map_expand"

    def __init__(self, input: Layer, num_filters: int, as_row_vector: bool = True,
                 act: Any = None, name=None):
        super().__init__(input, name=name)
        self.num_filters = num_filters
        self.as_row_vector = as_row_vector
        self.act = act

    def forward(self, ctx, ins):
        x = ins[0].value
        if self.as_row_vector:
            out = jnp.repeat(x[:, None, :], self.num_filters, axis=1)
            out = out.reshape(x.shape[0], -1)
        else:
            out = jnp.repeat(x, self.num_filters, axis=-1)
        return ins[0].with_value(act_mod.apply(self.act, out))


@LAYERS.register("resize")
class Resize(Layer):
    """ResizeLayer.cpp: reinterpret the whole [B, D] buffer as
    [B*D/size, size] — batch and feature trade off."""

    type_name = "resize"

    def __init__(self, input: Layer, size: int, name=None):
        super().__init__(input, name=name)
        self.size = size

    def forward(self, ctx, ins):
        x = ins[0].value
        total = x.size
        assert total % self.size == 0, (
            f"resize {self.name}: {tuple(x.shape)} has {total} elements, "
            f"not divisible by size={self.size}"
        )
        return Argument(x.reshape(-1, self.size))


@jax.custom_vjp
def _clip_grad(x, t):
    return x


def _clip_grad_fwd(x, t):
    return x, t


def _clip_grad_bwd(t, g):
    return jnp.clip(g, -t, t), None


_clip_grad.defvjp(_clip_grad_fwd, _clip_grad_bwd)


@LAYERS.register("error_clip")
class ErrorClip(Layer):
    """ExtraLayerAttribute.error_clipping_threshold: identity forward, the
    backpropagated error clipped to ±t (Layer.cpp backwardActivation's
    errorClipping). Chained by the layer_attr seam like dropout."""

    type_name = "error_clip"

    def __init__(self, input: Layer, threshold: float, name=None):
        super().__init__(input, name=name)
        self.threshold = float(threshold)

    def forward(self, ctx, ins):
        return ins[0].with_value(_clip_grad(ins[0].value, self.threshold))


@LAYERS.register("clip")
class Clip(Layer):
    """Elementwise clip (ClipLayer.cpp)."""

    type_name = "clip"

    def __init__(self, input: Layer, min: float, max: float, name=None):
        super().__init__(input, name=name)
        self.lo, self.hi = min, max

    def forward(self, ctx, ins):
        return ins[0].with_value(jnp.clip(ins[0].value, self.lo, self.hi))


@LAYERS.register("scale_shift")
class ScaleShift(Layer):
    """y = w*x + b with scalar learned w, optional scalar b
    (ScaleShiftLayer.cpp: bias only when biasParameter is set)."""

    type_name = "scale_shift"

    def __init__(self, input: Layer, bias: bool = True, param_attr=None,
                 bias_attr=None, name=None):
        super().__init__(input, name=name)
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx, ins):
        x = ins[0].value
        w = ctx.param(self, "w", (1,), init_mod.ones, self.param_attr)
        y = w[0] * x
        if self.bias:
            b = ctx.param(self, "b", (1,), init_mod.zeros, self.bias_attr)
            y = y + b[0]
        return ins[0].with_value(y)


@LAYERS.register("prelu")
class ParameterRelu(Layer):
    """Parametric ReLU with per-partition slopes (ParameterReluLayer.cpp;
    hl_param_relu_forward)."""

    type_name = "prelu"

    def __init__(self, input: Layer, partial_sum: int = 1, param_attr=None, name=None):
        super().__init__(input, name=name)
        self.partial_sum = partial_sum
        self.param_attr = _attr(param_attr)

    def forward(self, ctx, ins):
        x = ins[0].value
        d = x.shape[-1]
        n_slope = d // self.partial_sum
        w = ctx.param(self, "w", (n_slope,), init_mod.constant(0.25), self.param_attr)
        slopes = jnp.repeat(w, self.partial_sum)
        return ins[0].with_value(jnp.where(x > 0, x, x * slopes))


@LAYERS.register("multiplex")
class Multiplex(Layer):
    """Row-wise select among N inputs by index (MultiplexLayer.cpp):
    inputs[0] = int index [B], inputs[1..N] = candidates."""

    type_name = "multiplex"

    def __init__(self, index: Layer, inputs: Sequence[Layer], name=None):
        super().__init__([index] + list(inputs), name=name)

    def forward(self, ctx, ins):
        idx = ins[0].value.astype(jnp.int32).reshape(-1)
        stacked = jnp.stack([a.value for a in ins[1:]], axis=1)  # [B, N, D]
        out = jnp.take_along_axis(stacked, idx[:, None, None], axis=1)[:, 0]
        return ins[1].with_value(out)


@LAYERS.register("outer_prod")
class OuterProd(Layer):
    """Row-wise outer product flattened (OuterProdLayer.cpp)."""

    type_name = "outer_prod"

    def __init__(self, input1: Layer, input2: Layer, name=None):
        super().__init__([input1, input2], name=name)

    def forward(self, ctx, ins):
        a, b = ins[0].value, ins[1].value
        out = jnp.einsum("bi,bj->bij", a, b).reshape(a.shape[0], -1)
        return ins[0].with_value(out)


@LAYERS.register("conv_shift")
class ConvShift(Layer):
    """Circular 1-D correlation of each row with a learned/input kernel
    (ConvShiftLayer.cpp): out[i] = sum_j b[j] * a[(i+j-half) mod D]."""

    type_name = "conv_shift"

    def __init__(self, input1: Layer, input2: Layer, name=None):
        super().__init__([input1, input2], name=name)

    def forward(self, ctx, ins):
        a, b = ins[0].value, ins[1].value
        d = a.shape[-1]
        k = b.shape[-1]
        half = k // 2
        idx = (jnp.arange(d)[:, None] + jnp.arange(k)[None, :] - half) % d
        # out[b, i] = sum_j  a[b, idx[i,j]] * b[b, j]
        gathered = a[:, idx]  # [B, D, K]
        out = jnp.einsum("bdk,bk->bd", gathered, b)
        return ins[0].with_value(out)


@LAYERS.register("sum_to_one_norm")
class SumToOneNorm(Layer):
    """Row normalize to sum 1 (SumToOneNormLayer.cpp)."""

    type_name = "sum_to_one_norm"

    def forward(self, ctx, ins):
        x = ins[0].value
        return ins[0].with_value(x / jnp.maximum(jnp.sum(x, -1, keepdims=True), 1e-12))


@LAYERS.register("tensor")
class TensorLayer(Layer):
    """Bilinear tensor product (TensorLayer.cpp): out_k = x W_k y^T."""

    type_name = "tensor"

    def __init__(self, input1: Layer, input2: Layer, size: int, act=None,
                 bias: bool = True, param_attr=None, bias_attr=None, name=None):
        super().__init__([input1, input2], name=name)
        self.size = size
        self.act = act
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.bias_attr = _attr(bias_attr)

    def forward(self, ctx, ins):
        x, y = ins[0].value, ins[1].value
        w = ctx.param(
            self, "w", (self.size, x.shape[-1], y.shape[-1]),
            init_mod.smart_normal, self.param_attr,
        )
        out = jnp.einsum("bi,kij,bj->bk", x, w, y)
        if self.bias:
            b = ctx.param(self, "b", (self.size,), init_mod.zeros, self.bias_attr)
            out = out + b
        out = act_mod.apply(self.act, out)
        return ins[0].with_value(out)


@LAYERS.register("max_id")
class MaxId(Layer):
    """Argmax id of the last axis (MaxIdLayer.cpp); beam_size > 1 → top-k ids,
    matching the reference's beam output for generation."""

    type_name = "max_id"

    def __init__(self, input: Layer, beam_size: int = 1, name=None):
        super().__init__(input, name=name)
        self.beam_size = beam_size

    def forward(self, ctx, ins):
        x = ins[0].value
        if self.beam_size <= 1:
            out = jnp.argmax(x, axis=-1)
        else:
            out = jax.lax.top_k(x, self.beam_size)[1]
        return ins[0].with_value(out)


@LAYERS.register("sampling_id")
class SamplingId(Layer):
    """Sample an id from each row's probability distribution
    (SamplingIdLayer.cpp). Needs an rng in the apply context."""

    type_name = "sampling_id"

    def forward(self, ctx, ins):
        x = ins[0].value
        logits = jnp.log(jnp.maximum(x, 1e-30))
        ids = jax.random.categorical(ctx.next_rng(self.name), logits, axis=-1)
        return ins[0].with_value(ids)


@LAYERS.register("eos_id")
class EosIdCheck(Layer):
    """1 where the input id equals eos_id (EosIdCheckLayer.cpp)."""

    type_name = "eos_id"

    def __init__(self, input: Layer, eos_id: int, name=None):
        super().__init__(input, name=name)
        self.eos_id = eos_id

    def forward(self, ctx, ins):
        return ins[0].with_value(
            (ins[0].value == self.eos_id).astype(jnp.float32)
        )


@LAYERS.register("print")
class PrintLayer(Layer):
    """Debug-print its input during tracing/execution (PrintLayer.cpp) via
    jax.debug.print; passes the value through unchanged."""

    type_name = "print"

    def __init__(self, input: Layer, message: str = "", name=None):
        super().__init__(input, name=name)
        self.message = message

    def forward(self, ctx, ins):
        if ctx.mode == "init":  # config tracing/shape inference: stay quiet
            return ins[0]
        # escape user braces — only the {x} placeholder is a format field
        msg = self.message.replace("{", "{{").replace("}", "}}")
        jax.debug.print((msg + " {x}").lstrip(), x=ins[0].value)
        return ins[0]


@LAYERS.register("block_expand")
class BlockExpand(Layer):
    """Image → sequence of flattened blocks (BlockExpandLayer.cpp +
    paddle/function/BlockExpandOp.cpp, the im2col exposed as a layer — feeds
    OCR CRNN stacks). Input [B, H, W, C] → sequence [B, T, block_y*block_x*C]
    where T = out_h*out_w, scanned row-major like the reference."""

    type_name = "block_expand"

    def __init__(self, input: Layer, block_x: int, block_y: int,
                 stride_x: int = 0, stride_y: int = 0,
                 padding_x: int = 0, padding_y: int = 0, name=None):
        super().__init__(input, name=name)
        self.block = (block_y, block_x)
        self.stride = (stride_y or block_y, stride_x or block_x)
        self.padding = (padding_y, padding_x)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        b, h, w, c = x.shape
        (by, bx), (sy, sx), (py, px) = self.block, self.stride, self.padding
        x = jnp.pad(x, ((0, 0), (py, py), (px, px), (0, 0)))
        # XLA's patch extraction: conv_general_dilated_patches keeps it on MXU-
        # friendly layouts instead of a scalar gather loop
        patches = jax.lax.conv_general_dilated_patches(
            x, filter_shape=(by, bx), window_strides=(sy, sx), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # [B, out_h, out_w, C*by*bx]
        oh, ow = patches.shape[1], patches.shape[2]
        t = oh * ow
        seq = patches.reshape(b, t, patches.shape[-1])
        lengths = jnp.full((b,), t, jnp.int32)
        return Argument(seq, lengths)


@LAYERS.register("row_conv")
class RowConv(Layer):
    """Lookahead row convolution (RowConvLayer.cpp + function/RowConvOp.cpp,
    from DeepSpeech2): y[t] = sum_{i=0..ctx-1} x[t+i] * w[i], per feature —
    a depthwise causal-in-reverse conv done as one lax conv over time."""

    type_name = "row_conv"

    def __init__(self, input: Layer, context_len: int, act: Any = None,
                 param_attr: Any = None, name=None):
        super().__init__(input, name=name)
        self.context_len = context_len
        self.act = act
        self.param_attr = _attr(param_attr)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        arg = ins[0]
        x = arg.value  # [B, T, D]
        b, t, d = x.shape
        w = ctx.param(self, "w", (self.context_len, d), init_mod.smart_normal,
                      self.param_attr)
        # zero-pad the future edge; mask invalid (padded) timesteps so lookahead
        # never reads beyond a sequence's true length
        if arg.lengths is not None:
            x = x * arg.mask(x.dtype)[..., None]
        xp = jnp.pad(x, ((0, 0), (0, self.context_len - 1), (0, 0)))
        windows = jnp.stack(
            [xp[:, i : i + t, :] for i in range(self.context_len)], axis=0
        )  # [ctx, B, T, D]
        out = jnp.einsum("cbtd,cd->btd", windows, w.astype(x.dtype))
        out = act_mod.apply(self.act, out)
        return arg.with_value(out)


@LAYERS.register("selective_fc")
class SelectiveFc(Layer):
    """SelectiveFullyConnectedLayer.cpp: fc where only a selected subset of
    output columns is computed/valid. TPU-native form: compute the full matmul
    (MXU-friendly dense GEMM) and mask unselected columns to -inf/0 — the
    reference's sparse column GEMM is a bandwidth trick for CPUs that the MXU
    does not need at these sizes."""

    type_name = "selective_fc"

    def __init__(self, input, size: int, act: Any = None, bias: bool = True,
                 param_attr: Any = None, pass_generation: bool = False,
                 has_selected_colums: bool = True, selection_mode: str = "mask",
                 name=None):
        ins = input if isinstance(input, (list, tuple)) else [input]
        super().__init__(list(ins), name=name)
        self.size = size
        self.act = act
        self.bias = bias
        self.param_attr = _attr(param_attr)
        self.has_select = len(self.inputs) > 1

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        x = ins[0].value
        w = ctx.param(self, "w", (x.shape[-1], self.size),
                      init_mod.smart_normal, self.param_attr)
        out = linalg.matmul(x, w, ctx.policy)
        if self.bias:
            bvec = ctx.param(self, "b", (self.size,), init_mod.zeros, None)
            out = out + bvec
        sel = ins[1].value.astype(out.dtype) if self.has_select else None
        act_name = self.act if isinstance(self.act, str) else getattr(self.act, "name", self.act)
        if sel is not None and act_name == "softmax":
            # mask pre-activation so softmax normalizes over selected cols only
            # (SelectiveFullyConnectedLayer computes softmax on the selected set)
            out = jnp.where(sel > 0, out, jnp.asarray(-1e9, out.dtype))
            out = act_mod.apply(self.act, out)
            out = out * sel
        else:
            out = act_mod.apply(self.act, out)
            if sel is not None:
                out = out * sel
        return ins[0].with_value(out)
