"""Functional layer-graph core.

The TPU-native replacement for the reference's Layer/NeuralNetwork machinery
(paddle/gserver/layers/Layer.h:62 `forward`/`backward`; NeuralNetwork.cpp:245
forward = ordered loop over layers). Key design shift (SURVEY §7 "hard parts"):
instead of eager per-layer kernel calls, layers here are *pure specs*; the whole
forward pass is one traced JAX function, so XLA sees the entire step and fuses /
schedules it for the MXU. Backward is `jax.grad` of the traced forward — there are
no hand-written backward methods (the reference's per-layer `backward` and its
gradient-check harness become `jax.grad` + numeric-check tests).

Data between layers travels as `Argument` — the analog of paddle/parameter/Argument.h:26
(value + sequenceStartPositions). Ragged sequences become padded [B, T, ...] arrays
plus a per-example `lengths` vector (segment-id style), the TPU-friendly encoding of
`Argument.sequenceStartPositions` (Argument.h:84).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtypes

Array = jax.Array
Initializer = Callable[[jax.Array, Sequence[int], Any], Array]

# Reserved batch slot: [B] float 0/1 row-validity mask attached when a
# trailing batch is padded up to the mesh data-axis multiple
# (DataParallel.pad_batch). Network._run strips it into Context.sample_mask;
# cost layers weight per-example costs by it and normalize by the real row
# count, so padded rows contribute nothing to cost or gradients.
SAMPLE_MASK_KEY = "__sample_mask__"


# ---------------------------------------------------------------------------
# Argument: the inter-layer value (paddle/parameter/Argument.h:26)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Argument:
    """Value flowing between layers.

    value:   [B, ...] dense batch, or [B, T, ...] padded sequence batch.
    lengths: [B] int32 valid lengths when `value` is a sequence batch
             (replaces Argument.sequenceStartPositions, Argument.h:84).
    sub_lengths: [B, S] int32 for nested (sub-)sequences
             (replaces subSequenceStartPositions, Argument.h:91).
    """

    value: Array
    lengths: Optional[Array] = None
    sub_lengths: Optional[Array] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.value, self.lengths, self.sub_lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- helpers ------------------------------------------------------------
    @property
    def is_seq(self) -> bool:
        return self.lengths is not None

    @property
    def batch_size(self) -> int:
        return self.value.shape[0]

    @property
    def max_len(self) -> int:
        assert self.is_seq
        return self.value.shape[1]

    def mask(self, dtype=jnp.float32) -> Array:
        """[B, T] validity mask from lengths."""
        assert self.lengths is not None
        t = self.value.shape[1]
        return (jnp.arange(t)[None, :] < self.lengths[:, None]).astype(dtype)

    def with_value(self, value: Array) -> "Argument":
        return Argument(value, self.lengths, self.sub_lengths)

    def as_non_seq(self) -> "Argument":
        return Argument(self.value)


# ---------------------------------------------------------------------------
# ParamAttr (python/paddle/trainer_config_helpers/attrs.py ParamAttr)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ParamAttr:
    """Per-parameter attributes: sharing name, init, LR scale, decay, staticness.

    Mirrors the reference's ParameterConfig knobs (proto/ParameterConfig.proto:34:
    learning_rate, momentum, decay_rate(l2), decay_rate_l1, initial_std/mean,
    is_static, is_sparse) minus device placement, which is a sharding concern here.
    """

    name: Optional[str] = None  # set → parameter shared by this global name
    initializer: Optional[Initializer] = None
    initial_std: Optional[float] = None
    initial_mean: float = 0.0
    learning_rate: float = 1.0
    momentum: Optional[float] = None
    l1_decay: Optional[float] = None
    l2_decay: Optional[float] = None
    is_static: bool = False
    is_sparse: bool = False
    gradient_clipping_threshold: Optional[float] = None
    # uniform init range (ParameterConfig initial_min/initial_max); wins over
    # initial_std when set
    initial_min: Optional[float] = None
    initial_max: Optional[float] = None
    # NAMED logical sharding axes resolved through the parallel rules table
    # (parallel/rules.py DEFAULT_RULES), e.g. ("embed", "mlp") — declare the
    # axis MEANING once here; which mesh axis (if any) it shards over is the
    # deployment's rules-table decision (ISSUE 12).
    logical_axes: Optional[Tuple[Optional[str], ...]] = None
    # DEPRECATED: raw mesh-axis tuples, e.g. ("model", None). Kept as a shim —
    # mesh-axis names are implicitly logical names that resolve to themselves
    # through the rules table — so old call sites translate into the table
    # rather than bypassing it. New code should use logical_axes.
    sharding: Optional[Tuple[Optional[str], ...]] = None


# ---------------------------------------------------------------------------
# Context: parameter/state plumbing through a forward trace
# ---------------------------------------------------------------------------


class Context:
    """Threaded through a single forward trace.

    mode='init'  — creates parameters/states eagerly (concrete arrays).
    mode='apply' — reads from given pytrees; collects state updates (e.g.
                   batch-norm moving stats — the functional form of the mutable
                   movingMean_/movingVar_ in BatchNormalizationLayer).
    """

    def __init__(
        self,
        mode: str,
        params: Dict[str, Array],
        states: Dict[str, Array],
        rng: Optional[Array],
        train: bool,
        policy: Optional[dtypes.Policy] = None,
        param_resolver: Optional[Callable[[str, Array], Array]] = None,
    ):
        assert mode in ("init", "apply")
        self.mode = mode
        self.params = params
        self.states = states
        self.rng = rng
        self.train = train
        self.policy = policy or dtypes.current()
        # ZeRO-3 on-demand gather seam (ISSUE 14): in apply mode, a resolver
        # rebuilds a stored parameter's full view AT ITS POINT OF USE — the
        # Zero3Updater passes the all-gather of its flat data-axis-sharded
        # leaf, so each layer's gather is emitted next to its consumer in
        # the trace (layer-by-layer, not hoisted as one bulk gather) and the
        # backward's remat re-gathers per use. Memoized per trace below so a
        # SHARED parameter gathers once. None = params are stored full.
        self.param_resolver = param_resolver
        self.state_updates: Dict[str, Array] = {}
        self.param_attrs: Dict[str, ParamAttr] = {}
        self._rng_count = 0
        # per-trace scratch for composite layers that compute several outputs
        # at once (e.g. RecurrentGroup runs one scan shared by all its output
        # nodes); keyed by (id(core), tag)
        self.cache: Dict[Any, Any] = {}
        # [B] 0/1 weights from a padded batch (SAMPLE_MASK_KEY slot): cost
        # layers zero padded rows out of the loss and normalize by the REAL
        # row count, so a mesh-divisibility-padded batch reproduces the
        # unpadded batch's cost and gradients exactly
        self.sample_mask: Optional[Array] = None

    # -- rng ---------------------------------------------------------------
    def next_rng(self, tag: str) -> Array:
        if self.rng is None:
            raise ValueError("no rng available in this context (pass rng= to apply)")
        self._rng_count += 1
        return jax.random.fold_in(jax.random.fold_in(self.rng, _stable_hash(tag)), self._rng_count)

    # -- params ------------------------------------------------------------
    def param(
        self,
        layer: "Layer",
        pname: str,
        shape: Sequence[int],
        init: Initializer,
        attr: Optional[ParamAttr] = None,
    ) -> Array:
        attr = attr or ParamAttr()
        full = attr.name or f"{layer.name}.{pname}"
        if not hasattr(self, "param_owners"):
            self.param_owners = {}
        self.param_owners.setdefault((layer.name, pname), full)
        if self.mode == "init":
            if full not in self.params:
                initializer = attr.initializer or init
                if attr.initial_max is not None and attr.initializer is None:
                    lo = attr.initial_min if attr.initial_min is not None else -attr.initial_max
                    hi = attr.initial_max
                    initializer = (
                        lambda k, s, d: jax.random.uniform(
                            k, s, d, minval=lo, maxval=hi
                        )
                    )
                elif attr.initial_std is not None and attr.initializer is None:
                    std, mean = attr.initial_std, attr.initial_mean
                    initializer = (
                        lambda k, s, d: mean + std * jax.random.normal(k, s, d)
                    )
                elif attr.initializer is None and _param_default:
                    std = _param_default.get("initial_std")
                    mean = _param_default.get("initial_mean", 0.0)
                    if std is not None:
                        initializer = (
                            lambda k, s, d: mean + std * jax.random.normal(k, s, d)
                        )
                value = initializer(
                    self.next_rng(full), tuple(shape), self.policy.param_dtype
                )
                self.params[full] = value
                self.param_attrs[full] = attr
            else:
                got = tuple(self.params[full].shape)
                if got != tuple(shape):
                    raise ValueError(
                        f"shared parameter {full!r} shape mismatch: {got} vs {tuple(shape)}"
                    )
        value = self.params[full]
        if self.mode == "apply" and self.param_resolver is not None:
            key = ("__param_resolved__", full)
            if key not in self.cache:
                self.cache[key] = self.param_resolver(full, value)
            value = self.cache[key]
        return value

    # -- state (non-trainable, updated functionally) ------------------------
    def state(
        self,
        layer: "Layer",
        sname: str,
        shape: Sequence[int],
        init_value: Union[float, Array] = 0.0,
    ) -> Array:
        full = f"{layer.name}.{sname}"
        if self.mode == "init" and full not in self.states:
            self.states[full] = jnp.full(tuple(shape), init_value, dtype=jnp.float32)
        return self.states[full]

    def update_state(self, layer: "Layer", sname: str, value: Array) -> None:
        full = f"{layer.name}.{sname}"
        self.state_updates[full] = value


def _stable_hash(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = ((h ^ ch) * 16777619) & 0x7FFFFFFF
    return h


# ---------------------------------------------------------------------------
# Layer base + naming
# ---------------------------------------------------------------------------

_name_lock = threading.Lock()
_name_counters: Dict[str, int] = {}

# legacy config default init policy (config_parser default_initial_std/mean);
# consumed by Context.param when a parameter has no explicit init
_param_default: Dict[str, float] = {}


def _auto_name(type_name: str) -> str:
    with _name_lock:
        idx = _name_counters.get(type_name, 0)
        _name_counters[type_name] = idx + 1
    return f"__{type_name}_{idx}__"


def reset_name_scope() -> None:
    """Reset auto-name counters (call between independently-built graphs)."""
    _param_default.clear()
    with _name_lock:
        _name_counters.clear()


_record_tls = threading.local()


@contextlib.contextmanager
def record_layers(sink: List["Layer"]):
    """Collect every Layer constructed inside the block (used by
    recurrent_group to see step-net layers that are not output ancestors,
    e.g. a last_seq serving only as a memory link target)."""
    old = getattr(_record_tls, "sink", None)
    _record_tls.sink = sink
    try:
        yield sink
    finally:
        _record_tls.sink = old


class Layer:
    """A pure layer spec node in the graph.

    Subclasses implement `forward(ctx, ins) -> Argument`. No backward: autodiff
    handles it. `type_name` doubles as the registry key (REGISTER_LAYER analog).
    """

    type_name: str = "layer"
    # cost layers (scalar training objectives) mark themselves so the trainer
    # can split a config's Outputs() into costs vs plain fetches (the
    # reference's Outputs may mix both, sample_trainer_config_qb_rnn.conf)
    is_cost: bool = False

    def __init__(
        self,
        inputs: Union[None, "Layer", Sequence["Layer"]] = None,
        name: Optional[str] = None,
        **kwargs: Any,
    ):
        if inputs is None:
            inputs = []
        elif isinstance(inputs, Layer):
            inputs = [inputs]
        else:
            inputs = list(inputs)
        for i, l in enumerate(inputs):
            if not isinstance(l, Layer):
                raise TypeError(
                    f"{type(self).__name__} input {i} is {type(l).__name__}, not a Layer"
                )
        self.inputs: List[Layer] = inputs
        self.name = name or _auto_name(self.type_name)
        self.cfg = kwargs
        sink = getattr(_record_tls, "sink", None)
        if sink is not None:
            sink.append(self)

    def forward(self, ctx: Context, ins: List[Argument]) -> Argument:
        raise NotImplementedError

    def __repr__(self):
        return f"<{type(self).__name__} {self.name}>"


# ---------------------------------------------------------------------------
# Network: topological execution of a layer DAG
# ---------------------------------------------------------------------------


class Network:
    """Compiles a layer DAG into pure init/apply functions.

    The analog of NeuralNetwork (gserver/gradientmachines/NeuralNetwork.cpp:245):
    topological order once, then `apply` evaluates each layer exactly once. Unlike
    the reference, `apply` is pure and intended to be called *inside* jit/pjit so
    the whole step compiles to one XLA program (SURVEY §7 hard-part (1))."""

    def __init__(self, outputs: Union[Layer, Sequence[Layer]]):
        if isinstance(outputs, Layer):
            outputs = [outputs]
        self.outputs: List[Layer] = list(outputs)
        self.layer_order: List[Layer] = _topo_sort(self.outputs)
        self.layers_by_name: Dict[str, Layer] = {}
        for l in self.layer_order:
            if l.name in self.layers_by_name and self.layers_by_name[l.name] is not l:
                raise ValueError(f"duplicate layer name {l.name!r}")
            self.layers_by_name[l.name] = l
        self.param_attrs: Dict[str, ParamAttr] = {}
        self.fused_projections: Dict[str, Layer] = _plan_fused_projections(
            self.layer_order, self.outputs
        )

    # -- data layer discovery ----------------------------------------------
    @property
    def data_names(self) -> List[str]:
        return [l.name for l in self.layer_order if l.type_name == "data"]

    # -- init ---------------------------------------------------------------
    def init(
        self,
        rng: Array,
        batch: Dict[str, Union[Argument, Array, np.ndarray]],
        train: bool = True,
        policy: Optional[dtypes.Policy] = None,
    ) -> Tuple[Dict[str, Array], Dict[str, Array]]:
        """Create params/states by running forward eagerly on a sample batch.

        `policy` pins the dtype policy for this trace (mixed-precision
        trainers thread SGDTrainer(precision=...) through here); None falls
        back to the ambient dtypes.current() global. The whole trace runs
        under a policy_scope so nested ops that consult the ambient global
        themselves (ops/rnn, additive attention, beam search) follow THIS
        trace's policy, not whatever the process global happens to be."""
        policy = policy or dtypes.current()
        params: Dict[str, Array] = {}
        states: Dict[str, Array] = {}
        with dtypes.policy_scope(policy):
            ctx = Context("init", params, states, rng, train, policy=policy)
            self._run(ctx, batch)
        self.param_attrs = dict(ctx.param_attrs)
        return params, states

    # -- apply --------------------------------------------------------------
    def apply(
        self,
        params: Dict[str, Array],
        states: Dict[str, Array],
        batch: Dict[str, Any],
        train: bool = False,
        rng: Optional[Array] = None,
        policy: Optional[dtypes.Policy] = None,
        param_resolver: Optional[Callable[[str, Array], Array]] = None,
    ) -> Tuple[Dict[str, Argument], Dict[str, Array]]:
        """Pure forward. Returns ({output_layer_name: Argument}, new_states).

        Like init(), the trace is wrapped in a policy_scope so every nested
        dtypes.current() fallback resolves to this trace's policy.

        `param_resolver(name, stored_value)` rebuilds a parameter's full
        view at its point of use (Context.param) — the ZeRO-3 on-demand
        gather seam; None (default) means `params` already hold full
        values."""
        policy = policy or dtypes.current()
        with dtypes.policy_scope(policy):
            ctx = Context(
                "apply", params, states, rng, train, policy=policy,
                param_resolver=param_resolver,
            )
            values = self._run(ctx, batch)
        new_states = dict(states)
        new_states.update(ctx.state_updates)
        outs = {l.name: values[l.name] for l in self.outputs}
        return outs, new_states

    def _run(self, ctx: Context, batch: Dict[str, Any]) -> Dict[str, Argument]:
        from paddle_tpu.core import stack_trace

        if SAMPLE_MASK_KEY in batch:
            # reserved slot from a mesh-divisibility-padded batch: it feeds
            # the cost layers' masking via the context, never a data layer
            ctx.sample_mask = jnp.asarray(batch[SAMPLE_MASK_KEY])
            batch = {k: v for k, v in batch.items() if k != SAMPLE_MASK_KEY}
        values: Dict[str, Argument] = {}
        # init runs every layer as written: each makes its own parameters,
        # in its own place in the rng stream, and every layer has a value
        # (config/dump.py shapes each layer from it)
        fused = {} if ctx.mode == "init" else self.fused_projections
        given_away = {id(p) for p in fused.values()}
        for layer in self.layer_order:
            if layer.type_name == "data":
                values[layer.name] = _feed_to_argument(batch, layer)
                continue
            if id(layer) in given_away:
                continue  # its cost does its work, below
            projection = fused.get(layer.name)
            sources = list(layer.inputs)
            if projection is not None:
                sources[0] = projection.inputs[0]
            ins = [values[l.name] for l in sources]
            # layer-name crash context (CustomStackTrace parity,
            # NeuralNetwork.cpp:259-261)
            with stack_trace.layer_frame(layer.name):
                try:
                    if projection is not None:
                        out = layer.forward(ctx, ins, projection=projection)
                    else:
                        out = layer.forward(ctx, ins)
                except stack_trace.LayerError:
                    raise
                except Exception as e:
                    raise stack_trace.LayerError(
                        layer.name, stack_trace.current_stack(), e
                    ) from e
            if not isinstance(out, Argument):
                raise TypeError(
                    f"layer {layer.name} forward returned {type(out).__name__}"
                )
            values[layer.name] = out
        return values


def _plan_fused_projections(
    order: Sequence[Layer], outputs: Sequence[Layer]
) -> Dict[str, Layer]:
    """{cost layer's name: the linear projection it takes over}, decided
    once from the layer types and the graph: a cost that can fuse its
    projection (`fuses_projection`, costs.ClassificationCost from logits)
    does so when its input is a linear projection (`is_linear_projection`,
    layers.Fc with one input and no activation) whose value nothing else
    wants: no other consumer, not an output of this Network. The cost then
    runs on the projection's INPUT and the logits exist only inside
    ops/xent.linear_softmax_xent; the projection layer itself is skipped
    (in apply: init runs both as written, Network._run).
    Anything else (an evaluator or an extra output on the logits, a second
    cost on them) leaves both layers as they are."""
    consumers: Dict[int, int] = {}
    for layer in order:
        for src in layer.inputs:
            consumers[id(src)] = consumers.get(id(src), 0) + 1
    wanted = {id(o) for o in outputs}
    plan: Dict[str, Layer] = {}
    for layer in order:
        if not getattr(layer, "fuses_projection", False):
            continue
        src = layer.inputs[0]
        if (
            getattr(src, "is_linear_projection", False)
            and consumers[id(src)] == 1
            and id(src) not in wanted
        ):
            plan[layer.name] = src
    return plan


def _topo_sort(outputs: Sequence[Layer]) -> List[Layer]:
    order: List[Layer] = []
    seen: Dict[int, int] = {}  # id -> 0 visiting, 1 done

    def visit(l: Layer):
        key = id(l)
        st = seen.get(key)
        if st == 1:
            return
        if st == 0:
            raise ValueError(f"cycle in layer graph at {l.name}")
        seen[key] = 0
        for dep in l.inputs:
            visit(dep)
        seen[key] = 1
        order.append(l)

    for out in outputs:
        visit(out)
    return order


def _feed_to_argument(batch: Dict[str, Any], layer: Layer) -> Argument:
    if layer.name not in batch:
        raise KeyError(
            f"data layer {layer.name!r} missing from batch; got {sorted(batch)}"
        )
    v = batch[layer.name]
    if isinstance(v, Argument):
        return v
    v = jnp.asarray(v)
    lengths_key = layer.name + ".lengths"
    if lengths_key in batch:
        sub_key = layer.name + ".sub_lengths"
        sub = jnp.asarray(batch[sub_key]) if sub_key in batch else None
        return Argument(v, jnp.asarray(batch[lengths_key]), sub)
    return Argument(v)
