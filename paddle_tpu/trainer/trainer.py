"""Training driver.

Parity with paddle/trainer: Trainer::train (Trainer.cpp:261) / trainOnePass
(:492) / TrainerInternal::trainOneBatch (TrainerInternal.cpp:66), and the v2
API SGD.train (python/paddle/v2/trainer.py:24,:124).

TPU-native design (SURVEY §7 hard-part (1)): the whole hot loop —
forward, backward, optimizer update, LR schedule, model averaging — is ONE
compiled XLA program per batch shape, with the train state donated so
parameters update in-place in device memory. The reference's per-parameter
UpdateCallback chain is folded into that program. Data parallelism: pass a
`DataParallel` config (paddle_tpu/parallel) and the same step is pjit-sharded
over the mesh data axis; gradients all-reduce over ICI — the ring of
MultiGradientMachine.h:44-157 done by the hardware."""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtypes, faults, preempt, stats
from paddle_tpu.obs import metrics as obs_metrics
from paddle_tpu.obs import trace
from paddle_tpu.data.pipeline import StackedBatch
from paddle_tpu.data.pipeline import coerce_batch as _coerce_batch
from paddle_tpu.data.pipeline import is_device_batch
from paddle_tpu.nn.graph import SAMPLE_MASK_KEY, Argument, Layer, Network
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.optim.average import ModelAverage
from paddle_tpu.optim import schedules
from paddle_tpu.trainer import checkpoint as ckpt_mod
from paddle_tpu.trainer.events import BeginIteration, BeginPass, EndIteration, EndPass

log = logging.getLogger("paddle_tpu.trainer")

TrainState = Dict[str, Any]  # params / opt / states / avg / samples / rng

DIVERGENCE_POLICIES = ("skip_batch", "rollback", "raise")

_END_OF_PASS = object()  # next()'s default: the reader has no further item

# rematerialization policies for the compiled step's backward pass (see
# _build_step): "dots" keeps matmul/conv outputs and recomputes everything
# elementwise; "conv_only" keeps only the tagged conv/matmul outputs
# (ops/conv.py / ops/linalg.py checkpoint_name); "full" recomputes the whole
# forward. None/"none" = store every residual (jax default).
REMAT_POLICIES = (None, "none", "dots", "conv_only", "full")


SHARD_UPDATE_MODES = ("zero1", "zero2", "zero3")


def _resolve_shard_mode(shard_update) -> Optional[str]:
    """Normalize SGDTrainer(shard_update=...): bools stay the zero1 alias,
    strings name the ZeRO mode, anything else fails loudly."""
    if shard_update in (False, None, "none", "0"):
        return None
    if shard_update in (True, "true", "1"):
        return "zero1"
    if shard_update in SHARD_UPDATE_MODES:
        return shard_update
    raise ValueError(
        f"shard_update must be a bool or one of {SHARD_UPDATE_MODES}, got "
        f"{shard_update!r}"
    )


class DivergenceError(RuntimeError):
    """Raised by divergence_policy="raise" when a step cost goes NaN/Inf."""


class Preempted(RuntimeError):
    """Raised by train() after a preemption-notice drain (core/preempt):
    the in-flight step finished and — given a save_dir and remaining grace —
    a CRC-valid mid-pass checkpoint was written. The CLI maps this to exit
    code `preempt.EXIT_PREEMPTED`; a restart with auto_resume=True continues
    from exactly this batch boundary."""

    def __init__(
        self,
        pass_id: int,
        batches_done: int,
        checkpoint_dir: Optional[str],
        reason: Optional[str] = None,
    ):
        self.pass_id = pass_id
        self.batches_done = batches_done
        self.checkpoint_dir = checkpoint_dir
        self.reason = reason
        where = (
            f"checkpoint {checkpoint_dir}" if checkpoint_dir
            else "no checkpoint written"
        )
        super().__init__(
            f"preempted ({reason or 'signal'}) at pass {pass_id} after "
            f"{batches_done} batch(es); {where}"
        )


class SGDTrainer:
    """v2 `trainer.SGD` analog driving compiled train steps."""

    def __init__(
        self,
        cost: Union[Layer, Sequence[Layer]],
        optimizer: Optimizer,
        extra_outputs: Sequence[Layer] = (),
        schedule: Optional[Callable] = None,
        model_average: Optional[ModelAverage] = None,
        parallel: Optional[Any] = None,  # parallel.DataParallel or None
        updater: Optional[Any] = None,  # parallel.ParameterUpdater
        seed: int = 0,
        remat: Optional[str] = None,  # REMAT_POLICIES
        precision: Optional[str] = None,  # None (ambient) | "f32" | "bf16"
        divergence_policy: Optional[str] = None,  # skip_batch|rollback|raise
        guard_check_every: int = 16,  # steps between divergence-guard polls
        # ZeRO-sharded update over the mesh data axis: False/None = off,
        # True = "zero1" (back-compat alias), or "zero1"|"zero2"|"zero3"
        shard_update: Union[bool, str, None] = False,
        grad_compression: Optional[str] = None,  # None/none | bf16 | int8
    ):
        costs = [cost] if isinstance(cost, Layer) else list(cost)
        self.cost_names = [c.name for c in costs]
        self.extra_names = [e.name for e in extra_outputs]
        self.network = Network(costs + list(extra_outputs))
        self.optimizer = optimizer
        if remat not in REMAT_POLICIES:
            raise ValueError(
                f"remat must be one of {REMAT_POLICIES}, got {remat!r}"
            )
        self.remat = None if remat == "none" else remat
        # Mixed-precision policy (ISSUE 9): precision="bf16" makes THIS
        # trainer's compiled step cast dot/conv inputs to bfloat16 through
        # Policy.cast (ops/linalg.py, ops/conv.py) while parameters stay
        # float32 MASTERS — created f32, updated f32 by the optimizer
        # (update_one upcasts the incoming grad), stored f32 by checkpoints.
        # Gradients therefore flow bf16 through the backward network and land
        # f32 at the param leaves (the cast's transpose), so a bf16-trained
        # checkpoint resumes bitwise into an f32 trainer and vice versa.
        # Numerically-sensitive reductions stay pinned f32 regardless of the
        # policy: softmax/xent (ops/xent.py), batch-norm statistics
        # (ops/normalization.py), the pass-cost average and the divergence
        # guard's isfinite (both fed by the f32-pinned cost below).
        # None = inherit the ambient dtypes.current() global at build time
        # (init_ctx's dtype_policy flag, or a caller's dtypes.set_policy).
        self._policy_override = (
            dtypes.get(precision) if precision is not None else None
        )
        # The ParameterUpdater protocol (ParameterUpdater.h:38) is the seam
        # where parallelism plugs into the trainer: the optimizer application
        # inside the compiled step goes through updater.apply, and host-side
        # pass boundaries go through start_pass/finish_pass (barriers on
        # multi-host). Default: local updater, or the ICI all-reduce updater
        # when a DataParallel mesh is configured; shard_update selects a ZeRO
        # mode (parallel/updaters.py):
        #   "zero1" (True): reduce-scatter grads over the mesh data axis →
        #       shard-local optimizer step on 1/N of the optimizer state →
        #       all-gather updated params, every step;
        #   "zero2": zero1's update fused across the K-step dispatch — the
        #       multi-step program merges the window into one shard-local
        #       K*B batch, so grads cross the wire ONCE per dispatch
        #       (gradient-accumulation semantics: one update per window);
        #   "zero3": parameters live flat data-axis-sharded in the train
        #       state (~N x less param HBM per chip) and are gathered
        #       layer-by-layer on demand inside the step, re-gathered (not
        #       stored) in the backward via remat;
        # optionally with a compressed collective payload
        # (--grad_compression; parallel/compression.py — under zero3 the
        # int8 budget moves to the on-demand param gather).
        self.shard_update = _resolve_shard_mode(shard_update)
        if (
            self.shard_update or grad_compression not in (None, "none")
        ) and (parallel is None and updater is None):
            raise ValueError(
                "shard_update/grad_compression need a DataParallel mesh "
                "(SGDTrainer(parallel=...)): there is no data axis to shard "
                "the update over"
            )
        if grad_compression not in (None, "none") and not self.shard_update:
            raise ValueError(
                "grad_compression wraps the sharded update's reduce-scatter "
                "— pass shard_update=True with it"
            )
        if updater is not None and (
            self.shard_update or grad_compression not in (None, "none")
        ):
            raise ValueError(
                "shard_update/grad_compression select the built-in "
                "ShardedUpdater and cannot combine with an explicit "
                "updater= — construct ShardedUpdater(optimizer, parallel, "
                "compression=...) yourself instead"
            )
        if updater is None:
            from paddle_tpu.parallel import (
                IciAllReduceUpdater, SgdLocalUpdater, ShardedUpdater,
                Zero2Updater, Zero3Updater,
            )

            if parallel is not None and self.shard_update:
                cls = {
                    "zero1": ShardedUpdater,
                    "zero2": Zero2Updater,
                    "zero3": Zero3Updater,
                }[self.shard_update]
                updater = cls(
                    optimizer, parallel, compression=grad_compression or "none"
                )
            elif parallel is not None:
                updater = IciAllReduceUpdater(optimizer, parallel)
            else:
                updater = SgdLocalUpdater(optimizer)
        self.updater = updater
        self.schedule = schedule or schedules.build(optimizer.learning_rate)
        self.model_average = model_average or ModelAverage(0.0)
        self.parallel = parallel
        self.seed = seed
        # Divergence guard (SURVEY §5 failure-as-common-case): with a policy
        # set, the compiled step checks jnp.isfinite(cost), hands back the
        # PRE-step state on NaN/Inf (donation-safe — the select happens inside
        # the same program), and bumps a cumulative `diverged` counter carried
        # in the train state, so DETECTION is device-resident too. The host
        # polls that counter only every `guard_check_every` steps (and at pass
        # end / before a preempt drain) and reacts per policy within that
        # bounded window — no per-step host sync. guard_check_every=1 restores
        # the old react-at-the-offending-batch latency. None = guard compiled
        # out (the step program's async dispatch behavior stays byte-identical).
        if divergence_policy is not None and divergence_policy not in DIVERGENCE_POLICIES:
            raise ValueError(
                f"divergence_policy must be one of {DIVERGENCE_POLICIES} or "
                f"None, got {divergence_policy!r}"
            )
        self.divergence_policy = divergence_policy
        if guard_check_every < 1:
            raise ValueError(
                f"guard_check_every must be >= 1, got {guard_check_every}"
            )
        self.guard_check_every = guard_check_every
        self.state: Optional[TrainState] = None
        # set by resize_to: gates the per-dispatch stale-plan check on
        # StackedBatch groups — straggler batches sharded for an old mesh
        # can only exist once a resize happened in this process
        self._resized = False
        self._step_fn = None
        self._multi_fn = None  # K-step fused dispatch (make_multi_step), lazy
        self._eval_fn = None
        # host mirror of state["diverged"] as of the last guard poll — the
        # delta on each poll is the number of new divergence events
        self._diverged_seen = 0
        # background writer for async (zero-stall) checkpointing, created on
        # the first async save; wait() on it is the durability barrier
        self._ckpt_writer: Optional[ckpt_mod.AsyncCheckpointer] = None
        # (save_dir, pass_id) of the newest checkpoint this trainer wrote or
        # loaded — lets _rollback skip a full CRC re-scan per divergence event
        self._known_good_pass: Optional[tuple] = None
        # elastic resize bookkeeping: completed-epoch log (drain/reshard/
        # resume latency split, surfaced per pass in EndPass metrics) and the
        # in-flight marker consumed by the first post-reshard dispatch
        self._resize_log: List[Dict[str, Any]] = []
        self._resize_mark: Optional[Dict[str, Any]] = None

    # -- precision policy ----------------------------------------------------
    def policy(self) -> dtypes.Policy:
        """The dtype policy this trainer's programs trace under: the explicit
        SGDTrainer(precision=...) override, else the ambient global."""
        return self._policy_override or dtypes.current()

    @property
    def precision(self) -> str:
        return self.policy().name

    # -- state ---------------------------------------------------------------
    def init_state(self, sample_batch: Dict[str, Any]) -> TrainState:
        rng = jax.random.PRNGKey(self.seed)
        params, states = self.network.init(
            rng, sample_batch, train=True, policy=self.policy()
        )
        self.optimizer.param_attrs = self.network.param_attrs
        # the updater owns the opt-state LAYOUT: canonical per-param slots by
        # default, flat [n, chunk] data-axis-sharded slots (+ error-feedback
        # residuals) under shard_update. init_opt_state also binds the flat
        # geometry, which params_from_canonical below needs: under zero3 the
        # PARAMETERS adopt the same flat sharded layout (identity otherwise),
        # and the model-average state mirrors whatever layout params use.
        opt_state = self.updater.init_opt_state(params)
        params_store = self.updater.params_from_canonical(params)
        state: TrainState = {
            "params": params_store,
            "opt": opt_state,
            "states": states,
            "avg": self.model_average.init_state(params_store),
            # int32 (not float32): float32 absorbs small increments past 2^24
            # samples, which would freeze LR schedules and the per-step rng
            "samples": jnp.zeros((), jnp.int32),
            # host-adjustable LR multiplier: the rollback divergence policy
            # halves it on every restore (the classic diverged-run response)
            "lr_scale": jnp.ones((), jnp.float32),
            # device-resident divergence flag: cumulative count of steps whose
            # cost came back NaN/Inf (the step reverts those updates in-place);
            # the host reads it only at guard-poll boundaries
            "diverged": jnp.zeros((), jnp.int32),
            # on-device pass cost accumulator (guard mode): the step adds its
            # cost here and the divergence revert masks poisoned entries, so
            # the host never issues eager masking ops — one fetch per pass
            "cost_acc": jnp.zeros((), jnp.float32),
            "rng": rng,
        }
        self._diverged_seen = 0
        if self.parallel is not None:
            # hand the discovered per-param attrs (sharding specs) to the
            # parallel plan before placing the state on the mesh
            if not self.parallel.param_attrs:
                self.parallel.param_attrs = self.network.param_attrs
            # ZeRO-sharded slot/EF leaves land DIRECTLY on their 1/n-per-chip
            # resident placement via the updater's opt_leaf_sharding rule
            # (zero3 params/averages via param_leaf_sharding likewise)
            state = self.parallel.shard_state(
                state,
                opt_sharding=self.updater.opt_leaf_sharding,
                param_sharding=self.updater.param_leaf_sharding,
            )
        self.state = state
        return state

    # -- compiled step -------------------------------------------------------
    def _build_step(self):
        """The raw (untraced) train-step function; _make_step jits it and
        make_multi_step scans it."""
        net = self.network
        cost_names = self.cost_names
        extra_names = self.extra_names
        updater = self.updater
        schedule = self.schedule
        avg = self.model_average
        policy = self.policy()  # pinned at build time, like the remat choice

        def step(state: TrainState, batch: Dict[str, Any]):
            mask = batch.get(SAMPLE_MASK_KEY)
            # padded trailing batch: the samples counter advances by the REAL
            # row count (mask sum), so LR schedules and the per-step rng match
            # the unpadded run sample-for-sample
            bs = (
                _batch_size(batch)
                if mask is None
                # cast-ok: int counter arithmetic, not a precision boundary
                else jnp.sum(mask).astype(jnp.int32)
            )
            # cast-ok: int32 sample counter → f32 schedule input, policy-free
            lr = schedule(state["samples"].astype(jnp.float32)) * state["lr_scale"]
            step_rng = jax.random.fold_in(state["rng"], state["samples"])

            # ZeRO-3 gather seam: a non-None resolver makes Context.param
            # rebuild each flat sharded leaf's full view AT ITS POINT OF
            # USE inside Network.apply (layer-by-layer on demand; the
            # all-gather's transpose delivers already-scattered gradients
            # to updater.apply). None for every other updater.
            resolver = updater.param_resolver(state["opt"])

            def loss_fn(params):
                outs, new_states = net.apply(
                    params, state["states"], batch, train=True,
                    rng=step_rng, policy=policy, param_resolver=resolver,
                )
                total = sum(outs[c].value for c in cost_names)
                # the pass-cost average and the divergence guard's isfinite
                # are f32 reductions REGARDLESS of the compute policy; most
                # cost layers already reduce in f32 (ops/xent.py), this pin
                # is the contract for the rest
                # cast-ok: f32 pin of a sensitive reduction, not a narrowing
                return total.astype(jnp.float32), (outs, new_states)

            if self.remat == "dots":
                # generic remat policy: keep every dot/conv output (the MXU
                # work), recompute the elementwise rest in the backward pass
                # — frees the activation residuals between matmuls so the
                # saved HBM converts to larger per-chip batch
                loss_fn = jax.checkpoint(
                    loss_fn, policy=jax.checkpoint_policies.dots_saveable
                )
            elif self.remat == "conv_only":
                # bytes lever for bandwidth-bound convnets: keep conv/matmul
                # outputs (tagged "conv_out" in ops/conv.py and ops/linalg.py),
                # recompute the cheap BN/relu/add epilogues in the backward
                # pass instead of round-tripping them through HBM
                loss_fn = jax.checkpoint(
                    loss_fn,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "conv_out"
                    ),
                )
            elif self.remat == "full":
                loss_fn = jax.checkpoint(loss_fn)
            elif updater.mode == "zero3":
                # zero3 default (no explicit remat policy): save every
                # residual EXCEPT the gathered param views, so the backward
                # RE-GATHERS each full parameter instead of holding all of
                # them across the forward — the comms-for-memory trade that
                # makes the sharded residency real at peak, not just at
                # rest. The explicit policies above already recompute the
                # gathers (none of them saves the named views).
                loss_fn = jax.checkpoint(
                    loss_fn,
                    policy=jax.checkpoint_policies
                    .save_anything_except_these_names("zero3_gathered"),
                )

            (cost, (outs, new_states)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state["params"])
            if self.parallel is not None:
                grads, cost = self.parallel.reduce_grads(grads, cost)
            new_params, new_opt = updater.apply(
                grads, state["opt"], state["params"], lr
            )
            new_avg = avg.update(state["avg"], new_params)
            new_state = {
                "params": new_params,
                "opt": new_opt,
                "states": new_states,
                "avg": new_avg,
                "samples": state["samples"] + bs,
                "lr_scale": state["lr_scale"],
                "diverged": state["diverged"],
                "cost_acc": state["cost_acc"],
                "rng": state["rng"],
            }
            if self.divergence_policy is not None:
                # divergence guard, fully device-resident: on a NaN/Inf cost
                # every state leaf — params, opt slots, BN states, samples
                # counter, and the cost accumulator below — reverts to its
                # pre-step value, so the poisoned update never lands (and the
                # poisoned cost never joins the pass sum), while the
                # cumulative `diverged` counter ticks up. The host learns
                # about it at the next guard poll; no per-step value fetch.
                new_state["cost_acc"] = state["cost_acc"] + cost
                ok = jnp.isfinite(cost)
                new_state = jax.tree.map(
                    lambda new, old: jnp.where(ok, new, old), new_state, state
                )
                # cast-ok: int event counter, not a precision boundary
                new_state["diverged"] = state["diverged"] + jnp.where(
                    ok, 0, 1
                ).astype(jnp.int32)
            extras = {n: outs[n].value for n in extra_names}
            return new_state, cost, extras

        return step

    def _make_step(self):
        step = self._build_step()
        if self.parallel is not None:
            return self.parallel.compile_step(step)
        return jax.jit(step, donate_argnums=0)

    def make_multi_step(self):
        """K train steps per device dispatch: `multi(state, batches)` where
        every batch slot is stacked on a leading K axis, scanned with
        lax.scan inside ONE compiled program. Returns (new_state, costs[K]).
        On CPU the scan applies bitwise the same updates as K sequential
        single-step dispatches (tests/test_dispatch.py locks this in).

        This amortizes per-dispatch host latency (dominant on small-step
        workloads) and lets XLA overlap the tail of step i with
        the head of step i+1 — the TPU-native analog of the reference's
        compute/comm overlap in ConcurrentRemoteParameterUpdater
        (RemoteParameterUpdater.h:180). `train(steps_per_dispatch=K)` drives
        this program over K-batch groups from the reader (stacked by a
        DevicePrefetcher(stack_k=K) or host-side by the trainer).

        ZeRO-2 (shard_update="zero2") replaces the scan with the FUSED
        update: the K stacked batches merge into one shard-local [K*B] batch
        (each device's rows stay local — no batch reshuffle collective) and
        ONE forward/backward/update runs for the whole window, so the grad
        reduce-scatter and the param all-gather cross the wire once per
        DISPATCH instead of once per step (~K x fewer collective bytes on
        the grad leg). Semantics are classic gradient accumulation: the
        single update consumes the mean gradient over the window's real
        rows (sample masks compose exactly), parameters hold still within
        the window. Dispatch-level bookkeeping (cost accumulator, diverged
        counter) is scaled back to per-batch units inside the same program
        so pass averages and divergence accounting stay comparable to
        zero1; a poisoned window reverts and counts as K diverged steps."""
        step = self._build_step()

        if self.updater.mode == "zero2":
            guard_on = self.divergence_policy is not None
            n_data = self.parallel.data_axis_size
            batch_sharding = self.parallel._batch_sharding

            def multi(state: TrainState, batches: Dict[str, Any]):
                k = next(iter(batches.values())).shape[0]
                merged = {}
                for key, v in batches.items():
                    b = v.shape[1]
                    rest = tuple(v.shape[2:])
                    # shard-local merge [K, B] -> [K*B]: route the reshape
                    # through the data-axis split so each device's rows stay
                    # on-device (a naive k-major reshape would interleave
                    # shards and buy an all-to-all). Row order within the
                    # window changes, which a mean over the window cannot see.
                    vm = v.reshape((k, n_data, b // n_data) + rest)
                    vm = vm.transpose(
                        (1, 0, 2) + tuple(range(3, 3 + len(rest)))
                    )
                    merged[key] = jax.lax.with_sharding_constraint(
                        vm.reshape((k * b,) + rest), batch_sharding
                    )
                d0, a0 = state["diverged"], state["cost_acc"]
                new_state, cost, _ = step(state, merged)
                # one fused update stands for k batches: scale the dispatch-
                # level bookkeeping back to per-batch units (samples already
                # advanced by the window's real row count via the mask sum)
                new_state["diverged"] = d0 + (new_state["diverged"] - d0) * k
                if guard_on:
                    new_state["cost_acc"] = (
                        a0 + (new_state["cost_acc"] - a0) * k
                    )
                return new_state, jnp.broadcast_to(cost, (k,))

            return jax.jit(multi, donate_argnums=0)

        def multi(state: TrainState, batches: Dict[str, Any]):
            def body(s, b):
                s2, cost, _ = step(s, b)
                return s2, cost

            state, costs = jax.lax.scan(body, state, batches)
            return state, costs

        return jax.jit(multi, donate_argnums=0)

    def _make_eval(self):
        net = self.network
        cost_names = self.cost_names
        extra_names = self.extra_names
        avg = self.model_average
        policy = self.policy()

        updater = self.updater

        def evaluate(state: TrainState, batch: Dict[str, Any]):
            # zero3: averages share the flat layout, so averaging then
            # gathering equals gathering then averaging (it is linear)
            params = avg.averaged_params(state["avg"], state["params"])
            outs, _ = net.apply(
                params, state["states"], batch, train=False, policy=policy,
                param_resolver=updater.param_resolver(state["opt"]),
            )
            total = sum(outs[c].value for c in cost_names).astype(jnp.float32)
            extras = {n: outs[n].value for n in extra_names}
            return total, extras

        if self.parallel is not None:
            return self.parallel.compile_eval(evaluate)
        return jax.jit(evaluate)

    # -- public API ----------------------------------------------------------
    def train(
        self,
        reader: Callable,
        num_passes: int = 1,
        event_handler: Optional[Callable] = None,
        feeder: Optional[Callable] = None,
        test_reader: Optional[Callable] = None,
        save_dir: Optional[str] = None,
        log_period: int = 100,
        auto_resume: bool = False,
        keep_last_n: Optional[int] = None,
        steps_per_dispatch: int = 1,
        async_checkpoint: bool = True,
        resize_barrier: Optional[Callable] = None,
        remat: Optional[str] = None,
    ) -> TrainState:
        """reader yields batches (lists of samples if feeder given, else dicts
        of arrays). One call = `num_passes` passes (v1 --num_passes).

        auto_resume (needs save_dir): scan save_dir for the newest checkpoint
        that passes CRC — corrupt/partial pass dirs from a crashed save are
        skipped with a warning — restore params/opt/states and the pass and
        sample counters from it, and continue with the next pass. A run
        killed mid-pass and restarted this way replays the interrupted pass
        from its boundary and, with a deterministic reader, produces final
        params bitwise-identical to a never-killed run.

        steps_per_dispatch=K (>1): K consecutive same-shape batches are
        stacked and run through ONE compiled lax.scan dispatch
        (make_multi_step), amortizing per-dispatch host latency. Batches
        already stacked by a DevicePrefetcher(stack_k=K) dispatch as-is.
        Events, the recompile counter, the log line and the chaos sites
        (kill / preempt / nan_loss) all fire per-DISPATCH, not per batch:
        BeginIteration carries the first batch id of the window, EndIteration
        the last (its lazy .cost is the window's final cost; extra outputs
        are not collected on the fused path). A trailing remainder (pass end,
        shape change, reader exhaustion) runs through single-step dispatches,
        so a K-fused pass applies exactly the same updates as K=1.

        resize_barrier: fleet hook for elastic resize (see resize_to /
        runtime.master.ResizeClient). When a resize is requested
        (preempt.request_resize, set locally or by a master heartbeat
        watcher), the loop drains at the next dispatch boundary, writes a
        mid-pass checkpoint, calls `resize_barrier(req, pass_id,
        batches_done)` — which acks the master's drain barrier, blocks until
        every live trainer drained, and returns the final world size — then
        re-shards and CONTINUES the pass on the new mesh. None (default)
        resizes immediately to the requested world (single-trainer mode).

        async_checkpoint (default on): pass-boundary and preempt-drain saves
        copy the state to host with non-blocking fetches and hand all file
        I/O (npz/CRC/v1-format/manifest/retention) to a background writer
        thread, double-buffered with at most one snapshot in flight. train()
        waits for the writer before returning (and in its error path), load()
        and the preempt drain wait too, so every checkpoint path this method
        reports is durable. Writer failures re-raise on the training thread
        at the next save/wait.

        remat: re-pins the backward rematerialization policy for this and
        ALL SUBSEQUENT train() calls ("none" | "dots" | "conv_only" |
        "full" — see REMAT_POLICIES; it sticks on the trainer exactly like
        the constructor argument, pinned by test_train_remat_override_
        rebuilds_step). The recomputation replays the exact same ops, so
        switching remat changes step TIME and residual HBM, never the
        applied updates; compiled step programs are rebuilt when the policy
        changes. None (default) keeps the current setting."""
        event_handler = event_handler or (lambda e: None)
        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}"
            )
        if remat is not None:
            # per-call remat override (train(remat="none"|"dots"|"conv_only"|
            # "full")): re-pins the backward rematerialization policy and
            # drops any step programs compiled under the previous one
            if remat not in REMAT_POLICIES:
                raise ValueError(
                    f"remat must be one of {REMAT_POLICIES}, got {remat!r}"
                )
            resolved = None if remat == "none" else remat
            if resolved != self.remat:
                self.remat = resolved
                self._step_fn = None
                self._multi_fn = None
        resume_pass: Optional[int] = None
        resume_pending = False
        resume_mid = False  # checkpoint is a preemption-drain mid-pass save
        resume_skip = 0  # batches of resume_pass already applied (mid-pass drain)
        if auto_resume and save_dir is not None:
            if self._ckpt_writer is not None:
                self._ckpt_writer.wait()  # scan must see completed writes
            resume_pass = ckpt_mod.find_latest_valid_pass(save_dir)
            if resume_pass is not None:
                extra = ckpt_mod.pass_manifest(save_dir, resume_pass).get(
                    "extra", {}
                )
                if extra.get("mid_pass"):
                    # preemption-drain checkpoint: pass resume_pass is only
                    # partially applied — replay it from the drained boundary
                    resume_mid = True
                    resume_skip = int(extra.get("batches_done", 0))
                log.info(
                    "auto-resume: restoring from %s/pass-%05d (continuing at "
                    "pass %d%s)", save_dir, resume_pass,
                    resume_pass if resume_mid else resume_pass + 1,
                    f" batch {resume_skip}" if resume_mid else "",
                )
                if self.state is not None:
                    self.load(save_dir, resume_pass)
                    self._known_good_pass = (save_dir, resume_pass)
                else:  # state shapes unknown until the first batch arrives
                    resume_pending = True
        flushed = False
        try:
            for pass_id in range(num_passes):
                if resume_pass is not None and (
                    pass_id < resume_pass
                    or (pass_id == resume_pass and not resume_mid)
                ):
                    continue  # completed by the run we are resuming
                # span-ok: the pass is a real span, open while the pass runs,
                # so that every span of the pass (the prefetch worker's
                # too) has its trace id and a parent
                with trace.flight("train.pass", pass_id=pass_id) as pass_span:
                    resume_pending = self._train_one_pass(
                        reader, pass_id, event_handler, feeder, test_reader,
                        save_dir, log_period, keep_last_n, steps_per_dispatch,
                        async_checkpoint, resume_pass, resume_mid, resume_skip,
                        resume_pending, resize_barrier, pass_span,
                    )
            if resume_pending:
                # every requested pass was already checkpointed — nothing ran,
                # so state was never initialized; pull one batch just for
                # shapes and load the final checkpoint for the caller
                raw = next(iter(reader()), None)
                if raw is not None:
                    if isinstance(raw, StackedBatch):
                        raw = {k: v[0] for k, v in raw.items()}
                    on_device = is_device_batch(raw) and (
                        self.parallel is None
                        or self.parallel.is_sharded_batch(raw)
                    )
                    batch = (
                        raw
                        if on_device
                        else feeder(raw)
                        if feeder is not None and not isinstance(raw, dict)
                        else _coerce_batch(raw)
                    )
                    if self.parallel is not None and not on_device:
                        batch = self.parallel.shard_batch(batch)
                    self.init_state(batch)
                    self.load(save_dir, resume_pass)
                    self._known_good_pass = (save_dir, resume_pass)
            if self._ckpt_writer is not None:
                # durability barrier on the clean path: surfaces any async
                # write error and guarantees the final checkpoint is on disk
                self._ckpt_writer.wait()
            flushed = True
        finally:
            if not flushed and self._ckpt_writer is not None:
                # error path (incl. InjectedKill chaos): flush the in-flight
                # snapshot but never mask the propagating exception
                try:
                    self._ckpt_writer.wait()
                except Exception:
                    log.exception(
                        "async checkpoint flush failed during error exit"
                    )
        return self.state

    def _train_one_pass(
        self,
        reader: Callable,
        pass_id: int,
        event_handler: Callable,
        feeder: Optional[Callable],
        test_reader: Optional[Callable],
        save_dir: Optional[str],
        log_period: int,
        keep_last_n: Optional[int],
        steps_per_dispatch: int,
        async_checkpoint: bool,
        resume_pass: Optional[int],
        resume_mid: bool,
        resume_skip: int,
        resume_pending: bool,
        resize_barrier: Optional[Callable],
        pass_span: Any,
    ) -> bool:
        """One training pass of the async execution runtime. Returns the
        (possibly cleared) resume_pending flag.

        The pass accounts for its own time in obs/trace.py's ring, recorded
        without a switch: under `pass_span` (train()'s `train.pass`) every
        pull from the reader is a `train.input_wait` span, every call of the
        caller's event handler a `train.handler` span, every dispatch a
        `train.dispatch` span, beside `train.guard_poll`, `train.checkpoint`
        and the pass-end `train.cost_fetch`; what of the pass's duration
        none of them covers is the loop's own host work. A fixed number of
        ring writes per dispatch, no device sync, no formatting.

        Hot-loop discipline (enforced by tests/test_lint_hotloop.py): nothing
        in this body fetches a device value per step — cost accumulation is
        an async on-device add, divergence detection reads the carried
        `diverged` counter only at guard polls (_poll_guard), the log line is
        deferred one dispatch behind a non-blocking host copy, and avg_cost
        syncs once at pass end. Lines that DO fetch carry a `sync-ok` tag."""
        inj = faults.get()
        guard_on = self.divergence_policy is not None

        def emit(event) -> None:
            # span-ok: the caller's time (a handler may block on a device
            # value), kept apart from the loop's own
            with trace.flight("train.handler"):
                event_handler(event)

        emit(BeginPass(pass_id))
        self.updater.start_pass()
        stats.RECOMPILES.start_pass()
        t0 = time.time()
        cost_sum_dev = None
        if guard_on and self.state is not None:
            # zero the on-device pass cost accumulator (×0 keeps the leaf's
            # sharding); one tiny dispatch per pass, not per step
            self.state["cost_acc"] = self.state["cost_acc"] * 0
        stepped = 0  # batches whose update was dispatched this pass
        # per-pass padded-batch count as a DATA_EVENTS delta (same pattern as
        # divergence_events/FT_EVENTS): padding happens EITHER on this host
        # path or on a DevicePrefetcher worker — a local counter would read 0
        # whenever the prefetcher does the padding
        pass_pad0 = stats.DATA_EVENTS.get("padded_batches")
        pass_div0 = self._diverged_seen
        pass_rz0 = len(self._resize_log)  # resize epochs completed this pass
        steps_since_poll = 0
        pending: List[tuple] = []  # [(logical batch id, feed-ready batch)]
        pending_sig: Optional[tuple] = None  # shared signature of `pending`
        pending_log: Optional[tuple] = None  # deferred (pass, batch, cost_dev)
        logical = 0  # reader position in single-batch units
        boundary = 0  # resolved prefix: every earlier batch applied/skipped

        def flush_log() -> None:
            nonlocal pending_log
            if pending_log is not None:
                p, b, c = pending_log
                pending_log = None
                # sync-ok: deferred one dispatch behind; the value was copied
                # to host asynchronously at stash time, so this float() reads
                # an (almost always) already-landed buffer instead of
                # serializing the dispatch pipeline head
                log.info("pass %d batch %d cost=%.6f", p, b, float(c))

        def dispatch(idx_first: int, idx_last: int, batch, k: int) -> None:
            """One device dispatch: a single compiled step (k=1) or the
            K-step fused scan. Chaos sites, events, telemetry and the log
            line all operate at this granularity."""
            nonlocal cost_sum_dev, stepped, steps_since_poll, pending_log
            if inj.active:
                if inj.fire("kill"):
                    raise faults.InjectedKill(
                        f"injected kill at pass {pass_id} batch {idx_first}"
                    )
                if inj.fire("preempt"):
                    # simulated preemption notice (SIGTERM analog): only sets
                    # the drain flag — this dispatch still steps, the NEXT
                    # boundary checkpoints and exits ("finish the step")
                    preempt.get().request(
                        f"injected preempt at pass {pass_id} batch {idx_first}"
                    )
                if inj.fire("nan_loss"):
                    batch = _poison_batch(batch)
            # one distinct signature = one XLA trace+compile (the stacked
            # [K, B, ...] signature is its own program); churn past the
            # threshold warns (misconfigured seq_buckets)
            stats.RECOMPILES.record(stats.batch_signature(batch))
            emit(BeginIteration(pass_id, idx_first))
            # span-ok: one ring-buffer span per DISPATCH (constant name, int
            # attrs, no formatting); it measures the host's time to enqueue
            # the dispatch, not device time (no sync)
            with trace.flight("train.dispatch", first=idx_first, k=k):
                if k == 1:
                    self.state, cost, extras = self._step_fn(self.state, batch)
                    costs = None
                else:
                    if self._multi_fn is None:
                        self._multi_fn = self.make_multi_step()
                    self.state, costs = self._multi_fn(self.state, batch)
                    cost, extras = costs[-1], {}
            obs_metrics.observe_train_dispatch()
            if self._resize_mark is not None:
                # first dispatch on the post-resize mesh returned (compile
                # included): close the resume leg of the resize latency split
                self._note_resize_resumed()
            # pass-cost accumulation never syncs: in guard mode the compiled
            # step itself accumulates state["cost_acc"] (with the divergence
            # revert masking poisoned entries), otherwise accumulate with one
            # async on-device add per dispatch — the batch-count correction
            # for masked entries happens at pass end from the guard delta
            if not guard_on:
                contrib = costs.sum() if costs is not None else cost
                cost_sum_dev = (
                    contrib if cost_sum_dev is None else cost_sum_dev + contrib
                )
            stepped += k
            steps_since_poll += k
            suppress = False
            if guard_on and steps_since_poll >= self.guard_check_every:
                steps_since_poll = 0
                new = self._poll_guard(pass_id, idx_last, save_dir)
                # per-step polling of an unfused step: the window IS this
                # batch, so restore the old event contract — a poisoned batch
                # joins neither cost nor events nor the log line. Wider
                # windows still deliver the dispatch's event (its lazy .cost
                # may read non-finite; see events.EndIteration).
                suppress = bool(new) and k == 1 and self.guard_check_every == 1
            if suppress:
                return
            emit(EndIteration(pass_id, idx_last, cost, extras))
            if idx_last % log_period < k:  # window crossed a log_period mark
                flush_log()
                cost.copy_to_host_async()  # start D2H without blocking
                pending_log = (pass_id, idx_last, cost)

        def flush_pending() -> None:
            """Run buffered (ungrouped) batches through single-step
            dispatches — the trailing-remainder / shape-churn path."""
            nonlocal boundary, pending_sig
            for idx, b in pending:
                dispatch(idx, idx, b, 1)
            if pending:
                boundary = pending[-1][0] + 1
                del pending[:]
            pending_sig = None

        items = iter(reader())
        while True:
            # span-ok: one ring write per item pulled: the time the loop
            # waited for data (the reader's own work, or the prefetch queue)
            with trace.flight("train.input_wait") as wait:
                raw = next(items, _END_OF_PASS)
                wait.attrs = {"batch": logical}
            obs_metrics.observe_input_wait(wait.dur_ns * 1e-9)
            if raw is _END_OF_PASS:
                break
            k_item = raw.k if isinstance(raw, StackedBatch) else 1
            idx0 = logical
            logical += k_item
            if preempt.requested():
                # dispatch boundary: the previous step completed; drain —
                # checkpoint (mid-pass) and raise Preempted. The current raw
                # batch and any still-buffered ones are unprocessed and
                # replay after resume. Inside a replayed prefix the restored
                # state already holds resume_skip batches — never report
                # fewer, or the next resume would re-apply some of them.
                done = boundary
                if resume_mid and pass_id == resume_pass:
                    done = max(done, resume_skip)
                self._drain_preempt(
                    save_dir, pass_id, done, keep_last_n, async_checkpoint
                )
            if self.state is not None and preempt.resize_requested():
                # elastic resize at the same boundary discipline, but
                # COOPERATIVE: buffered batches flush on the old mesh first
                # (they were padded for its data axis), then _drain_resize
                # checkpoints, passes the fleet barrier, re-shards, and
                # returns — the current raw batch runs on the NEW mesh
                flush_pending()
                done = boundary
                if resume_mid and pass_id == resume_pass:
                    done = max(done, resume_skip)
                self._drain_resize(
                    save_dir, pass_id, done, keep_last_n, async_checkpoint,
                    resize_barrier,
                )
                rebind = getattr(reader, "rebind_parallel", None)
                if rebind is not None:
                    # a DevicePrefetcher keeps padding/sharding for the mesh
                    # it was built with — point it at the post-resize plan so
                    # only its <= depth in-flight batches take the straggler
                    # rebuild path, not the rest of the run (no-op when the
                    # resize was rejected or claimed elsewhere)
                    rebind(self.parallel)
                if cost_sum_dev is not None and self.parallel is not None:
                    # migrate the pass-cost accumulator: an array committed
                    # to the old mesh cannot join new-mesh computations
                    cost_sum_dev = self.parallel.replicate(cost_sum_dev)
            if (
                resume_skip
                and pass_id == resume_pass
                and idx0 + k_item <= resume_skip
            ):
                # replayed prefix of the preempted pass: these batches are
                # already folded into the restored state — consume the
                # (deterministic) reader past them without stepping
                boundary = logical
                continue
            if isinstance(raw, StackedBatch):
                # prefetcher-stacked group: device-resident [K, B, ...] slots
                if self.state is None:
                    self.init_state({k: v[0] for k, v in raw.items()})
                    if resume_pending:  # deferred auto-resume load
                        self.load(save_dir, resume_pass)
                        self._known_good_pass = (save_dir, resume_pass)
                        resume_pending = False
                if self._step_fn is None:
                    self._step_fn = self._make_step()
                flush_pending()  # keep update order = reader order
                skip = 0
                if resume_skip and pass_id == resume_pass and idx0 < resume_skip:
                    skip = resume_skip - idx0  # group straddles the boundary
                mismatched = (
                    self._resized
                    and self.parallel is not None
                    and not self.parallel.is_sharded_batches(dict(raw))
                )
                if skip or mismatched:
                    for j in range(skip, k_item):
                        b = {k: v[j] for k, v in raw.items()}
                        if mismatched:
                            # post-resize straggler from a prefetcher still
                            # bound to the OLD mesh: its slots are committed
                            # to old-mesh devices and padded to the old
                            # shard multiple — rebuild each sub-batch on
                            # host and re-pad/re-shard for the current plan
                            # instead of feeding the new compiled program
                            # incompatible arrays
                            b = {k: np.asarray(v) for k, v in b.items()}
                            b = self.parallel.maybe_pad_batch(
                                b,
                                where=f"train batch {idx0 + j} (post-resize)",
                            )
                            if b is None:
                                continue
                            b = self.parallel.shard_batch(b)
                        dispatch(idx0 + j, idx0 + j, b, 1)
                else:
                    # plain dict: the subclass is a marker, not a pytree node
                    dispatch(idx0, idx0 + k_item - 1, dict(raw), k_item)
                boundary = logical
                continue
            batch_id = idx0
            # device batches (from a DevicePrefetcher) arrive fed, sharded
            # and resident — skip the whole host prep leg; dict batches are
            # already feed-ready (e.g. from a DoubleBuffer that ran the
            # feeder on its prefetch thread). Under DataParallel the fast
            # path additionally requires the mesh batch sharding —
            # device-resident but unsharded arrays still go through
            # shard_batch below.
            on_device = is_device_batch(raw) and (
                self.parallel is None or self.parallel.is_sharded_batch(raw)
            )
            if on_device:
                batch = raw  # fed and put by the prefetcher's worker
            else:
                batch = (
                    feeder(raw)
                    if feeder is not None and not isinstance(raw, dict)
                    else _coerce_batch(raw)
                )
            if self.parallel is not None and not on_device:
                # trailing partial batch not divisible by the mesh data axis
                # pads to the next shard multiple with a 0/1 row mask (cost
                # layers zero the pad rows and normalize by the real count),
                # so the batch TRAINS and pass averages/sample counts match
                # the unsharded run — the old drop_last skip lost those
                # samples every pass; only unpaddable ragged batches drop
                batch = self.parallel.maybe_pad_batch(
                    batch, where=f"train batch {batch_id}"
                )
                if batch is None:
                    if not pending:
                        boundary = logical
                    continue
                batch = self.parallel.shard_batch(batch)
            if self.state is None:
                self.init_state(batch)
                if resume_pending:  # deferred auto-resume load
                    self.load(save_dir, resume_pass)
                    self._known_good_pass = (save_dir, resume_pass)
                    resume_pending = False
            if self._step_fn is None:
                self._step_fn = self._make_step()
            if steps_per_dispatch == 1:
                dispatch(batch_id, batch_id, batch, 1)
                boundary = logical
                continue
            # K-step grouping: buffer same-shape batches until K are ready,
            # then stack them into one fused scan dispatch. A shape change
            # flushes the buffer through single steps first (stacking needs
            # homogeneous shapes, and update order must follow reader order).
            sig = stats.batch_signature(batch)
            if pending and sig != pending_sig:
                flush_pending()
            pending.append((batch_id, batch))
            pending_sig = sig
            if len(pending) == steps_per_dispatch:
                stacked = _stack_batches([b for _, b in pending])
                if self.parallel is not None:
                    stacked = self.parallel.shard_batches(stacked)
                dispatch(pending[0][0], pending[-1][0], stacked,
                         steps_per_dispatch)
                boundary = pending[-1][0] + 1
                del pending[:]
                pending_sig = None
        flush_pending()  # trailing remainder: fewer than K batches left
        if self._resize_mark is not None:
            # resize landed at the pass's last boundary — no dispatch after
            # it; close the split with the (near-zero) resume leg here
            self._note_resize_resumed()
        # final guard poll: the bounded reaction window never crosses a pass
        # boundary (the pass-end checkpoint must not absorb unexamined NaNs)
        if guard_on and self.state is not None:
            self._poll_guard(pass_id, max(logical - 1, 0), save_dir)
        flush_log()
        n_diverged = self._diverged_seen - pass_div0
        n_batches = stepped - n_diverged
        if guard_on and self.state is not None:
            cost_sum_dev = self.state["cost_acc"]  # step-accumulated, masked
        # span-ok: once a pass; the fetch waits for the last dispatch to run,
        # which is the device's time and not the loop's
        with trace.flight("train.cost_fetch"):
            avg_cost = (
                # sync-ok: the single pass-end fetch of the on-device sum
                float(cost_sum_dev) / n_batches
                if n_batches and cost_sum_dev is not None
                else 0.0
            )
        metrics: Dict[str, Any] = {
            "avg_cost": avg_cost,
            "batches": n_batches,
            "pass_seconds": time.time() - t0,
            "shape_signatures": stats.RECOMPILES.pass_signatures(),
            "divergence_events": n_diverged,
            "padded_batches": (
                stats.DATA_EVENTS.get("padded_batches") - pass_pad0
            ),
        }
        pass_resizes = self._resize_log[pass_rz0:]
        if pass_resizes:
            # elastic resize observability: epochs completed this pass and
            # their drain/re-shard/resume latency split (chaos_bench --mode
            # resize reads these; the fleet aggregate gets the same numbers
            # via obs_metrics.observe_resize on the heartbeat snapshot)
            metrics["resize_epochs"] = len(pass_resizes)
            metrics["resizes"] = pass_resizes
        if self.parallel is not None and self.state is not None:
            # memory/comms observability for the sharded update: per-chip
            # resident bytes from sharding METADATA (no device sync — hot-loop
            # discipline holds, this is pass-end bookkeeping), modeled
            # collective bytes from the updater, HBM peak where the backend
            # reports it (TPU memory_stats; {} on CPU)
            metrics["param_bytes"] = stats.per_chip_tree_bytes(
                self.state["params"]
            )
            metrics["opt_state_bytes"] = stats.per_chip_tree_bytes(
                self.state["opt"]
            )
            metrics["collective_bytes_per_step"] = (
                self.updater.collective_bytes_per_step(steps_per_dispatch)
            )
            detail = self.updater.collective_bytes_detail(steps_per_dispatch)
            if detail:
                # per-leg (scatter/gather) x mode (zero1/2/3) x dtype
                # breakdown of the modeled collective traffic
                metrics["collective_bytes_detail"] = detail
            hbm = stats.device_memory_stats()
            if hbm.get("peak_bytes_in_use"):
                metrics["peak_hbm_bytes"] = hbm["peak_bytes_in_use"]
        pass_span.attrs["batches"] = n_batches
        self.updater.finish_pass()
        if test_reader is not None:
            metrics["test_cost"] = self.test(test_reader, feeder)["cost"]
        if save_dir is not None:
            self.save(
                save_dir, pass_id, keep_last_n=keep_last_n,
                async_=async_checkpoint,
            )
            self._known_good_pass = (save_dir, pass_id)
        emit(EndPass(pass_id, metrics))
        return resume_pending

    def _poll_guard(
        self,
        pass_id: int,
        batch_id: int,
        save_dir: Optional[str],
        react: bool = True,
    ) -> int:
        """Divergence-guard poll: read the device-resident cumulative
        `diverged` counter (the ONE sanctioned guard sync) and react to the
        delta since the last poll. The in-step guard already reverted every
        poisoned update on device, so by the time the host learns about a
        window's divergences the state is clean — the reaction here is
        policy, not protection. Returns the number of new events."""
        with trace.flight("train.guard_poll", batch=batch_id):
            d = int(self.state["diverged"])  # sync-ok: the guard-poll site
        new = d - self._diverged_seen
        self._diverged_seen = d
        if new <= 0:
            return 0
        stats.FT_EVENTS.incr("divergence", new)
        if not react:
            # preempt drain: record the events but do not rollback/raise —
            # the in-step guard already protected the checkpointed state
            log.warning(
                "divergence guard: %d non-finite step cost(s) detected while "
                "draining at pass %d batch %d — updates were reverted on "
                "device; no policy reaction during the drain",
                new, pass_id, batch_id,
            )
            return new
        if self.divergence_policy == "raise":
            raise DivergenceError(
                f"non-finite cost in {new} step(s) within the guard window "
                f"ending at pass {pass_id} batch {batch_id}; every poisoned "
                f"update was rolled back to its pre-step state on device"
            )
        if self.divergence_policy == "rollback":
            self._rollback(save_dir, pass_id, batch_id)
        else:
            log.warning(
                "divergence guard: non-finite cost in %d step(s) in the "
                "window ending at pass %d batch %d — poisoned updates were "
                "skipped on device", new, pass_id, batch_id,
            )
        return new

    def _drain_preempt(
        self,
        save_dir: Optional[str],
        pass_id: int,
        batches_done: int,
        keep_last_n: Optional[int],
        async_checkpoint: bool = False,
    ) -> None:
        """Preemption drain at a dispatch boundary: persist a mid-pass
        checkpoint (CRC-valid, `latest`-pointed) unless the grace budget is
        already spent, then raise Preempted. The save syncs the device, so
        the checkpoint holds the state AFTER the just-finished step; with
        async_checkpoint the writer is waited on before raising, so the
        exit-77 checkpoint is durable before the process dies."""
        guard = preempt.get()
        saved: Optional[str] = None
        if self.state is not None and self.divergence_policy is not None:
            # fold any unexamined guard window into telemetry before the
            # state is persisted (no policy reaction mid-drain)
            self._poll_guard(pass_id, batches_done, save_dir, react=False)
        if self.state is not None and save_dir is not None:
            if guard.deadline_passed():
                log.warning(
                    "preempt drain at pass %d batch %d: grace budget (%.1fs) "
                    "already spent — exiting WITHOUT a mid-pass checkpoint; "
                    "resume replays from the last durable one",
                    pass_id, batches_done, guard.grace_s,
                )
            else:
                saved = self.save(
                    save_dir, pass_id, keep_last_n=keep_last_n,
                    mid_pass_batches=batches_done, async_=async_checkpoint,
                )
                if self._ckpt_writer is not None:
                    self._ckpt_writer.wait()  # durable before exit 77
                self._known_good_pass = (save_dir, pass_id)
        stats.FT_EVENTS.incr("preempt_drain")
        log.warning(
            "preempt drain: stopping at pass %d batch %d (%s)",
            pass_id, batches_done,
            f"checkpointed to {saved}" if saved else "no checkpoint",
        )
        raise Preempted(pass_id, batches_done, saved, guard.reason)

    # -- elastic resize (ISSUE 8) --------------------------------------------
    def resize_to(self, world: int, devices: Optional[Sequence] = None) -> None:
        """Re-shard the LIVE train state onto a mesh whose data axis spans
        `world` chips — the elastic-resize seam. Values are preserved
        exactly: params/states/counters are replicated (placement-only move),
        and optimizer slots cross through the updater's canonical per-param
        layout (PR 5's checkpoint-portability seam) before re-flattening for
        the new shard count, so a resized run resumes bitwise from where the
        old mesh stopped. Compiled step/eval programs are dropped and rebuilt
        lazily for the new mesh. Composes with shard_update (the
        ShardedUpdater rebinds its [n, chunk] geometry) and K-step dispatch
        (the multi-step program rebuilds too)."""
        assert self.state is not None, "resize_to needs live state"
        if self.parallel is None:
            raise ValueError(
                "resize_to needs a DataParallel trainer "
                "(SGDTrainer(parallel=...)): there is no mesh to re-shape"
            )
        from paddle_tpu.parallel import DataParallel
        from paddle_tpu.parallel.mesh import resize_mesh

        old = self.parallel
        new_mesh = resize_mesh(old.mesh, old.batch_axis, world, devices)
        new_parallel = DataParallel(
            new_mesh, batch_axis=old.batch_axis, param_attrs=old.param_attrs,
            rules=old.rules,
        )
        # canonical layout is the portable waypoint: gather ZeRO-flat
        # slots — and zero3's flat params — back to parameter shapes on
        # the OLD updater...
        canonical = self.updater.to_canonical(self.state["opt"])
        params_canonical = self.updater.params_to_canonical(
            self.state["params"]
        )
        # model averages mirror the param layout (flat under zero3), so
        # they cross the resize through the same seam (identity otherwise)
        avg_canonical = (
            self.updater.params_to_canonical(self.state["avg"]["avg"])
            if self.state.get("avg")
            else None
        )
        if faults.get().fire("reshard_kill"):
            # chaos hook: the process dies mid-re-shard — after the
            # drain checkpoint, before the new mesh runs; auto_resume
            # must replay the pass from the drained boundary on the new
            # world size
            raise faults.InjectedKill("injected reshard_kill (chaos)")
        # ...then re-flatten for the NEW shard count and place every
        # leaf on its new-mesh sharding (ZeRO leaves land directly
        # 1/n-resident). rebind derives geometry from CANONICAL shapes.
        new_updater = self.updater.rebind(new_parallel, params_canonical)
        state = dict(self.state)
        state["opt"] = new_updater.from_canonical(canonical)
        state["params"] = new_updater.params_from_canonical(params_canonical)
        if avg_canonical is not None:
            state["avg"] = {
                **state["avg"],
                "avg": new_updater.params_from_canonical(avg_canonical),
            }
        self.parallel = new_parallel
        self.updater = new_updater
        self.state = new_parallel.shard_state(
            state,
            opt_sharding=new_updater.opt_leaf_sharding,
            param_sharding=new_updater.param_leaf_sharding,
        )
        self._step_fn = None
        self._multi_fn = None
        self._eval_fn = None
        self._resized = True

    def _drain_resize(
        self,
        save_dir: Optional[str],
        pass_id: int,
        batches_done: int,
        keep_last_n: Optional[int],
        async_checkpoint: bool,
        barrier: Optional[Callable] = None,
    ) -> None:
        """Cooperative resize drain at a dispatch boundary (NO process exit):
        fold any open guard window, persist a durable mid-pass checkpoint (a
        crash during the re-shard resumes from exactly this boundary), pass
        the fleet drain barrier (when master-coordinated), re-shard onto the
        new world size, and return to the train loop — the interrupted pass
        continues on the new mesh with the very next batch."""
        req = preempt.get().take_resize()
        if req is None:
            return  # another poller claimed it
        if self.parallel is None:
            log.warning(
                "resize request (%s) ignored: this trainer has no "
                "DataParallel mesh to re-shape", req.reason,
            )
            return
        if self.state is not None and self.divergence_policy is not None:
            # unexamined guard window folds into telemetry before the state
            # crosses the mesh boundary (no policy reaction mid-drain)
            self._poll_guard(pass_id, batches_done, save_dir, react=False)
        saved: Optional[str] = None
        if self.state is not None and save_dir is not None:
            saved = self.save(
                save_dir, pass_id, keep_last_n=keep_last_n,
                mid_pass_batches=batches_done, async_=async_checkpoint,
            )
            if self._ckpt_writer is not None:
                self._ckpt_writer.wait()  # durable BEFORE the mesh moves
            self._known_good_pass = (save_dir, pass_id)
        if barrier is None:
            # local mode has no _drain_barrier leg, so the stall site hooks
            # here; fleet mode stalls inside the barrier itself (one hook
            # point per drain, never both)
            faults.maybe_stall("resize_drain_stall")
            world = req.world
        else:
            # fleet mode: ack `resize_drained` and block until the master's
            # go (every live trainer drained or was evicted); the returned
            # world supersedes the announced one after membership churn
            world = int(barrier(req, pass_id, batches_done))
        t_drained = time.monotonic()
        trace.span_from_monotonic(
            "train.resize.drain", req.requested_at,
            attrs={"epoch": req.epoch, "pass": pass_id, "batch": batches_done},
        )
        stats.FT_EVENTS.incr("resize_drain")
        if world == self.parallel.data_axis_size:
            # drain-only epoch (membership churn cancelled out, or the
            # fleet decided the size this trainer already runs): nothing to
            # re-shard — and no reason to pay a recompile for a no-op
            log.info(
                "resize epoch %d: already at world %d — drain-only, no "
                "re-shard", req.epoch, world,
            )
        else:
            try:
                self.resize_to(world)
            except ValueError as e:
                # a bad announce (e.g. join/evict policy counting TRAINERS
                # on a host without that many devices) must reject the
                # resize, not kill a drained-and-checkpointed trainer
                # mid-pass; training continues on the current mesh
                stats.FT_EVENTS.incr("resize_rejected")
                log.error(
                    "resize epoch %d to world=%d rejected: %s — continuing "
                    "the pass on the current %d-chip mesh",
                    req.epoch, world, e, self.parallel.data_axis_size,
                )
                return
        t_resharded = time.monotonic()
        trace.span_from_monotonic(
            "train.resize.reshard", t_drained, attrs={"world": world},
        )
        log.warning(
            "resize drain at pass %d batch %d (%s): %s; data axis now %d "
            "chip(s) (epoch %d) — resuming the interrupted pass",
            pass_id, batches_done, req.reason,
            f"checkpointed to {saved}" if saved else "no checkpoint",
            world, req.epoch,
        )
        self._resize_mark = {
            "epoch": req.epoch,
            "world": world,
            "pass": pass_id,
            "batch": batches_done,
            "drain_s": t_drained - req.requested_at,
            "reshard_s": t_resharded - t_drained,
            "t_resharded": t_resharded,
        }

    def _note_resize_resumed(self) -> None:
        """Close out an in-flight resize once the first post-re-shard
        dispatch returned (or at pass end when the resize was the pass's
        last boundary): records the resume leg of the latency split, the
        resize span/metrics, and the per-pass log entry."""
        m, self._resize_mark = self._resize_mark, None
        resume_s = time.monotonic() - m["t_resharded"]
        trace.span_from_monotonic(
            "train.resize.resume", m["t_resharded"],
            attrs={"epoch": m["epoch"], "world": m["world"]},
        )
        split = {
            "drain": m["drain_s"], "reshard": m["reshard_s"],
            "resume": resume_s,
        }
        obs_metrics.observe_resize(split)
        stats.FT_EVENTS.incr("resize_epoch")
        self._resize_log.append({
            "epoch": m["epoch"],
            "world": m["world"],
            "pass": m["pass"],
            "batch": m["batch"],
            "drain_s": round(split["drain"], 6),
            "reshard_s": round(split["reshard"], 6),
            "resume_s": round(split["resume"], 6),
        })
        log.info(
            "resize epoch %d complete: world=%d drain=%.3fs reshard=%.3fs "
            "resume=%.3fs", m["epoch"], m["world"], split["drain"],
            split["reshard"], split["resume"],
        )

    def _rollback(self, save_dir: Optional[str], pass_id: int, batch_id: int) -> None:
        """Divergence rollback: restore the newest valid checkpoint and halve
        the LR multiplier; with no checkpoint to return to, degrade to
        skip_batch (the in-step guard already protected the state)."""
        latest: Optional[int] = None
        if save_dir is not None and self._ckpt_writer is not None:
            # an async save of THIS trainer may still be in flight — the scan
            # and the load below must only ever see completed writes
            self._ckpt_writer.wait()
        if save_dir is not None:
            # last checkpoint this trainer wrote/loaded needs no CRC re-scan
            # (a stream of NaN batches would otherwise re-read the whole
            # checkpoint set once per diverged step)
            if self._known_good_pass and self._known_good_pass[0] == save_dir:
                latest = self._known_good_pass[1]
            else:
                latest = ckpt_mod.find_latest_valid_pass(save_dir)
        if latest is None:
            log.warning(
                "divergence rollback at pass %d batch %d: no valid checkpoint "
                "under %r — falling back to skipping the batch",
                pass_id, batch_id, save_dir,
            )
            return
        cur_scale = float(self.state["lr_scale"])
        try:
            self.load(save_dir, latest)
        except (OSError, ValueError):
            # the remembered checkpoint rotted on disk — fall back to a scan
            self._known_good_pass = None
            latest = ckpt_mod.find_latest_valid_pass(save_dir)
            if latest is None:
                log.warning(
                    "divergence rollback at pass %d batch %d: no valid "
                    "checkpoint under %r — falling back to skipping the batch",
                    pass_id, batch_id, save_dir,
                )
                return
            self.load(save_dir, latest)
        # halve from the LOWER of the live and checkpointed scales, so
        # back-to-back rollbacks onto the same checkpoint keep compounding
        # (0.5 → 0.25 → …) instead of resetting to the stored value
        self.state["lr_scale"] = jnp.asarray(
            min(cur_scale, float(self.state["lr_scale"])) * 0.5, jnp.float32
        )
        stats.FT_EVENTS.incr("divergence_rollback")
        log.warning(
            "divergence rollback at pass %d batch %d: restored pass-%05d, "
            "lr_scale now %g", pass_id, batch_id, latest,
            float(self.state["lr_scale"]),
        )

    def test(self, reader: Callable, feeder: Optional[Callable] = None) -> Dict[str, Any]:
        """Tester analog (paddle/trainer/Tester.cpp): average cost over a reader."""
        assert self.state is not None, "call train() or init_state() first"
        if self._eval_fn is None:
            self._eval_fn = self._make_eval()
        total, n = 0.0, 0
        for raw in reader():
            on_device = is_device_batch(raw) and (
                self.parallel is None or self.parallel.is_sharded_batch(raw)
            )
            batch = (
                raw
                if on_device
                else feeder(raw)
                if feeder is not None and not isinstance(raw, dict)
                else _coerce_batch(raw)
            )
            if self.parallel is not None and not on_device:
                # same pad+mask treatment as training: the masked cost is
                # the mean over REAL rows only
                batch = self.parallel.maybe_pad_batch(batch, where="test batch")
                if batch is None:
                    continue
                batch = self.parallel.shard_batch(batch)
            cost, _ = self._eval_fn(self.state, batch)
            if SAMPLE_MASK_KEY in batch:
                # padded batch (here or on a prefetcher worker): real rows
                # only — the masked cost is already the mean over them. The
                # sum runs as an eager device op so a mesh-sharded mask works
                # on multi-host too (the result is a replicated, addressable
                # scalar; np.asarray on the global mask would raise there)
                bs = int(jnp.sum(jnp.asarray(batch[SAMPLE_MASK_KEY])))
            else:
                bs = _batch_size(batch)
            total += float(cost) * bs
            n += bs
        return {"cost": total / max(n, 1), "samples": n}

    def save(
        self,
        save_dir: str,
        pass_id: int,
        keep_last_n: Optional[int] = None,
        mid_pass_batches: Optional[int] = None,
        async_: bool = False,
    ) -> str:
        """Raw params + optimizer + averaging state are all persisted so
        load() is a true resume; deployment-time averaged weights are
        recoverable via ModelAverage.averaged_params on the loaded state.

        mid_pass_batches marks a preemption-drain save: the pass is only
        applied through that many batches, and auto-resume replays the rest
        of it instead of skipping to the next pass.

        async_=True is the zero-stall path: the state is copied to host with
        non-blocking fetches (copy_to_host_async per leaf, so the D2H
        transfers overlap each other), then npz/CRC/v1-format/manifest/
        retention run on a background writer thread, double-buffered with at
        most one snapshot in flight. The returned path is durable only after
        checkpoint_wait(); train()/load()/the preempt drain invoke that
        barrier themselves."""
        assert self.state is not None
        # the checkpoint span covers what the TRAINING THREAD pays: the full
        # write when synchronous, only the D2H fetch + enqueue when async
        with trace.flight("train.checkpoint", pass_id=pass_id, is_async=async_):
            # checkpoints always store the CANONICAL per-param layout: a
            # ShardedUpdater gathers its flat [n, chunk] slot/EF shards back
            # to parameter shapes here — and the Zero3Updater its flat
            # PARAMS too — so the same pass dir resumes under any
            # shard_update mode (and across device counts) bitwise
            params_store = self.updater.params_to_canonical(
                self.state["params"]
            )
            opt_tree = {"opt": self.updater.to_canonical(self.state["opt"])}
            if self.state["avg"]:
                opt_tree["avg"] = {
                    **self.state["avg"],
                    "avg": self.updater.params_to_canonical(
                        self.state["avg"]["avg"]
                    ),
                }
            extra_meta = {
                "samples": int(self.state["samples"]),
                "lr_scale": float(self.state["lr_scale"]),
                # world-size provenance: canonical checkpoints LOAD across
                # world sizes (the resize story), but load() uses this to
                # give a precise error when a non-canonical/foreign opt tree
                # sneaks in with the wrong shard count
                "world_size": (
                    self.parallel.data_axis_size
                    if self.parallel is not None else 1
                ),
            }
            if mid_pass_batches is not None:
                extra_meta["mid_pass"] = True
                extra_meta["batches_done"] = int(mid_pass_batches)
            if not async_:
                return ckpt_mod.save_pass(
                    save_dir,
                    pass_id,
                    params_store,
                    self.state["states"],
                    opt_tree,
                    extra_meta=extra_meta,
                    keep_last_n=keep_last_n,
                )
            if self._ckpt_writer is None:
                self._ckpt_writer = ckpt_mod.AsyncCheckpointer()
            params_np = _fetch_host_tree(params_store)
            states_np = _fetch_host_tree(self.state["states"])
            opt_np = _fetch_host_tree(opt_tree)
            return ckpt_mod.save_pass_async(
                self._ckpt_writer,
                save_dir,
                pass_id,
                params_np,
                states_np,
                opt_np,
                extra_meta=extra_meta,
                keep_last_n=keep_last_n,
            )

    def checkpoint_wait(self) -> None:
        """Durability barrier for async saves: returns once no checkpoint
        write is in flight, re-raising any writer failure. No-op when async
        checkpointing was never used."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()

    def load(self, save_dir: str, pass_id: Optional[int] = None) -> None:
        """Resume values, optimizer slots (when the structure matches) and the
        samples counter from a checkpoint — a true resume, unlike the v1
        reference which checkpoints only parameter values (SURVEY §5
        'Optimizer state ... is not checkpointed in v1')."""
        assert self.state is not None, "init_state() with a sample batch first"
        self.checkpoint_wait()  # never read a checkpoint that is mid-write
        params, states, opt_flat, manifest = ckpt_mod.load_pass(
            save_dir, pass_id,
            # lazy canonical template: only the legacy v1-binary branch needs
            # shapes, and building them under zero3 would eagerly gather the
            # flat-sharded params (a transient full-model footprint on the
            # COMMON native-format resume path otherwise)
            params_template=lambda: self.updater.params_to_canonical(
                self.state["params"]
            ),
        )
        self.state["params"] = self.updater.params_from_canonical(
            {k: jnp.asarray(v) for k, v in params.items()}
        )
        if states:
            self.state["states"] = {k: jnp.asarray(v) for k, v in states.items()}
        if opt_flat:
            # restore against the canonical layout (what save() wrote), then
            # re-flatten for a ShardedUpdater — identity for the others
            template = {"opt": self.updater.to_canonical(self.state["opt"])}
            if self.state["avg"]:
                template["avg"] = {
                    **self.state["avg"],
                    "avg": self.updater.params_to_canonical(
                        self.state["avg"]["avg"]
                    ),
                }
            # pin the cross-world-size contract: canonical checkpoints load
            # on ANY world size, so a shape mismatch here means the opt tree
            # was written as raw per-shard state (pre-canonical or foreign)
            # — restore_tree would silently keep freshly-initialized slots,
            # which is a wrong resume; fail loudly instead, naming shapes
            # and shard counts
            def _raw_shard_error(reason: str) -> ValueError:
                found_world = manifest.get("extra", {}).get("world_size")
                mine = (
                    self.parallel.data_axis_size
                    if self.parallel is not None else 1
                )
                return ValueError(
                    f"checkpoint under {save_dir!r} holds optimizer state "
                    f"that does not match this trainer's canonical layout: "
                    f"{reason}. The checkpoint records world_size="
                    f"{found_world}, this trainer runs world_size={mine}; "
                    f"canonical checkpoints are world-size-portable, so the "
                    f"opt tree was saved as raw per-shard state — re-export "
                    f"it through the updater's to_canonical seam before "
                    f"resuming"
                )

            def _clip(items):
                more = f" (+{len(items) - 4} more)" if len(items) > 4 else ""
                return items[:4], more

            mism = ckpt_mod.tree_shape_mismatches(template, opt_flat)
            if mism:
                head, more = _clip(mism)
                detail = "; ".join(
                    f"{k}: expected {exp} found {got}"
                    for k, exp, got in head
                )
                raise _raw_shard_error(f"{detail}{more}")
            missing = [
                k for k in ckpt_mod.tree_missing_keys(template, opt_flat)
                if k.startswith("opt")
            ]
            if missing:
                all_opt = ckpt_mod.tree_missing_keys(
                    {"opt": template["opt"]}, {}
                )
                head, more = _clip(missing)
                names = ", ".join(head)
                if len(missing) == len(all_opt):
                    # zero key overlap: restore_tree would restore NOTHING
                    # and the trainer would resume on entirely fresh slots
                    # — the foreign-writer / raw-per-shard failure mode the
                    # shape guard cannot see (no common key to compare)
                    raise _raw_shard_error(
                        f"no entry for {names}{more}, so restore_tree "
                        f"would silently keep freshly-initialized slots"
                    )
                # partial overlap is the documented lenient contract:
                # slots resume when the structure matches, structure new
                # since the save (e.g. momentum turned on) starts fresh —
                # say so instead of doing it silently
                log.warning(
                    "checkpoint %s: optimizer tree has no entry for %s%s; "
                    "those slots start freshly initialized, everything "
                    "else resumes",
                    save_dir, names, more,
                )
            restored = ckpt_mod.restore_tree(template, opt_flat)
            self.state["opt"] = self.updater.from_canonical(restored["opt"])
            if "avg" in restored:
                self.state["avg"] = {
                    **restored["avg"],
                    "avg": self.updater.params_from_canonical(
                        restored["avg"]["avg"]
                    ),
                }
        samples = manifest.get("extra", {}).get("samples")
        if samples is not None:
            self.state["samples"] = jnp.asarray(int(samples), jnp.int32)
        lr_scale = manifest.get("extra", {}).get("lr_scale")
        if lr_scale is not None:
            self.state["lr_scale"] = jnp.asarray(float(lr_scale), jnp.float32)
        if self.parallel is not None:
            # re-establish mesh placement (sharded head weights, replicated
            # or ZeRO-flat slots/params) — plain asarray loads land
            # unsharded otherwise
            self.state = self.parallel.shard_state(
                self.state,
                opt_sharding=self.updater.opt_leaf_sharding,
                param_sharding=self.updater.param_leaf_sharding,
            )


def _stack_batches(batches: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Stack K same-shape feed-ready batches on a new leading K axis for one
    fused scan dispatch. Host batches stack with numpy; device-resident ones
    (e.g. singles from a prefetcher) with jnp so the stack stays on device."""
    first = batches[0]
    stack = jnp.stack if is_device_batch(first) else np.stack
    return {k: stack([b[k] for b in batches]) for k in first}


def _fetch_host_tree(tree: Any) -> Any:
    """Device tree → numpy tree with overlapped D2H: every leaf's transfer is
    started non-blocking first, then the results are gathered — the training
    thread waits only for the DMA, never for file I/O.

    The gather must be a REAL copy (np.array, not np.asarray): on the CPU
    backend asarray can alias the device buffer, and the next pass's donated
    step would overwrite the "snapshot" under the async writer's feet."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    return jax.tree.map(
        lambda leaf: np.array(leaf) if isinstance(leaf, jax.Array)
        else np.asarray(leaf),
        tree,
    )


def _batch_size(batch: Dict[str, Any]) -> int:
    for k, v in batch.items():
        if not k.endswith(".lengths"):
            return int(np.shape(v)[0])
    raise ValueError("empty batch")


def _poison_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
    """nan_loss chaos hook: NaN out the first float slot (shape and dtype
    unchanged, so no recompile) — the realistic corrupt-sample fault the
    divergence guard exists for."""
    out = dict(batch)
    for k, v in batch.items():
        if not k.endswith(".lengths") and np.issubdtype(
            np.dtype(getattr(v, "dtype", np.asarray(v).dtype)), np.floating
        ):
            out[k] = v * np.float32("nan")
            return out
    raise ValueError("nan_loss fault: batch has no float slot to poison")
