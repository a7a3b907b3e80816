"""`paddle` CLI — TrainerMain parity.

Reference: the `paddle train` entry (paddle/scripts/submit_local.sh.in:96-116 →
paddle_trainer, paddle/trainer/TrainerMain.cpp:32) driven by gflags
(utils/Flags.h:19-43), plus `--job=time` benchmarking (TrainerBenchmark.cpp)
and model tools (MergeModel.cpp, python/paddle/utils/dump_config.py).

Usage:
    python -m paddle_tpu train --config=conf.py [--config_args=k=v,...]
        [--num_passes=N] [--save_dir=DIR] [--trainer_count=N] [--use_tpu=1]
        [--init_model_path=DIR] [--start_pass=N] [--log_period=N] [--job=train|test|time]
        [--auto_resume=1] [--divergence_policy=skip_batch|rollback|raise]
        [--shard_update=zero1|zero2|zero3] [--grad_compression=none|bf16|int8]
        [--precision=f32|bf16] [--remat=none|dots|conv_only|full]
        [--guard_check_every=N] [--steps_per_dispatch=K] [--async_checkpoint=0|1]
        [--keep_last_n=N] [--faults=SPEC]
        [--master_endpoints=a:p1,b:p2] [--preempt_grace_s=S] [--elastic=1]
        [--profile=pass:N] [--profile_dir=DIR]
    python -m paddle_tpu dump_config --config=conf.py
    python -m paddle_tpu merge_model --config=conf.py --model_dir=DIR --output=FILE
    python -m paddle_tpu serve [--port=N] [--demo | --load=model.npz]
        [--config=conf.py --model_dir=DIR] [--max_slots=N] [--page_size=N]
        [--prefill_buckets=16,32,64] [--max_new_limit=N] [--max_queue=N]
        [--tenant_tokens=CAP] [--tenant_tokens_per_s=R] [--tenant_concurrent=N]
        [--lease_s=S] [--require_register=0|1]
    python -m paddle_tpu version
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Callable, List, Optional

from paddle_tpu import proto


def _str2bool(v: str) -> bool:
    return str(v).lower() in ("1", "true", "yes", "on")


def _shard_update_mode(v: str):
    """--shard_update value: bools stay the zero1 alias (back-compat),
    zero1/zero2/zero3 name the ZeRO mode explicitly."""
    s = str(v).strip().lower()
    if s in ("zero1", "zero2", "zero3"):
        return s
    if s in ("1", "true", "yes", "on"):
        return "zero1"
    if s in ("0", "false", "no", "off", "none", ""):
        return False
    raise argparse.ArgumentTypeError(
        f"--shard_update must be a boolean or one of zero1/zero2/zero3, "
        f"got {v!r}"
    )


def _train_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", required=True, help="config script path")
    p.add_argument("--config_args", default="", help="k=v,... passed to get_config_arg")
    p.add_argument("--use_tpu", type=_str2bool, default=True)
    p.add_argument("--use_gpu", type=_str2bool, default=None, help="v1 alias of --use_tpu")
    p.add_argument("--trainer_count", type=int, default=1)
    p.add_argument("--num_passes", type=int, default=1)
    p.add_argument("--save_dir", default=None)
    p.add_argument("--init_model_path", default=None)
    p.add_argument("--start_pass", type=int, default=0)
    p.add_argument("--log_period", type=int, default=100)
    p.add_argument("--test_period", type=int, default=0)
    p.add_argument("--saving_period", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", default=None, choices=[None, "float32", "bfloat16"])
    p.add_argument(
        "--precision", default=None, choices=[None, "f32", "bf16"],
        help="mixed-precision policy for THIS trainer's compiled step: bf16 "
             "casts dot/conv inputs to bfloat16 (the MXU-native path) while "
             "parameters stay float32 masters in the optimizer and in "
             "checkpoints — a bf16-trained checkpoint resumes bitwise into an "
             "f32 run and vice versa. Softmax/xent, batch-norm statistics, "
             "cost averaging and the divergence guard stay f32 regardless. "
             "Default: f32 (or the process-wide --dtype policy when set)",
    )
    p.add_argument(
        "--remat", default=None,
        choices=[None, "none", "dots", "conv_only", "full"],
        help="backward rematerialization policy: 'dots' keeps matmul/conv "
             "outputs and recomputes the elementwise rest (frees activation "
             "residual HBM for larger per-chip batch), 'conv_only' keeps "
             "only tagged conv outputs, 'full' recomputes the whole forward. "
             "Recomputation replays the same ops, so the applied updates "
             "never change — only step time and residual memory",
    )
    p.add_argument("--job", default="train", choices=["train", "test", "time"])
    p.add_argument("--num_batches", type=int, default=20, help="--job=time batches")
    p.add_argument(
        "--prefetch_depth", type=int, default=2,
        help="device-resident batches to prefetch ahead of the train step "
             "(0 disables the async input pipeline)",
    )
    p.add_argument(
        "--steps_per_dispatch", type=int, default=1,
        help="train steps fused into one compiled device dispatch "
             "(lax.scan over K prefetcher-stacked batches); events, the "
             "log line and chaos sites then fire per dispatch, not per "
             "batch. 1 = one dispatch per batch",
    )
    p.add_argument(
        "--shard_update", type=_shard_update_mode, default=False,
        help="ZeRO-sharded weight update over the mesh data axis. "
             "zero1 (or 1/true, the back-compat alias): reduce-scatter "
             "grads, shard-local optimizer step on 1/N of the optimizer "
             "state (resident sharded — ~N x less opt-state HBM per chip), "
             "all-gather updated params. zero2: zero1 fused across the "
             "--steps_per_dispatch window — one scatter/gather per dispatch "
             "(~K x fewer grad-leg bytes; gradient-accumulation semantics). "
             "zero3: params themselves live data-axis-sharded (~N x less "
             "param HBM per chip), gathered layer-by-layer on demand inside "
             "the step and re-gathered in the backward. Needs "
             "--trainer_count > 1 to matter",
    )
    p.add_argument(
        "--grad_compression", default="none",
        choices=["none", "bf16", "int8"],
        help="quantize the sharded update's collective payloads: bf16 "
             "halves both legs (~2x fewer collective bytes/step); int8 "
             "block-scales the gradient leg with an error-feedback "
             "residual in the train state (~2.7x total); under "
             "--shard_update=zero3 int8 instead quantizes INSIDE the "
             "on-demand param all-gather (the hot leg there, ~3.75x) with "
             "a master-tracking EF residual. Requires --shard_update",
    )
    p.add_argument(
        "--guard_check_every", type=int, default=16,
        help="steps between divergence-guard polls of the device-resident "
             "diverged counter (reaction latency vs throughput; 1 = react "
             "at the offending batch like the old per-step sync). Only "
             "meaningful with --divergence_policy",
    )
    p.add_argument(
        "--async_checkpoint", type=_str2bool, default=True,
        help="write pass/drain checkpoints on a background thread after a "
             "non-blocking device→host fetch (zero-stall); 0 = synchronous "
             "writes on the training thread",
    )
    p.add_argument(
        "--auto_resume", type=_str2bool, default=False,
        help="on startup, resume from the newest CRC-valid checkpoint under "
             "--save_dir (corrupt/partial pass dirs are skipped)",
    )
    p.add_argument(
        "--divergence_policy", default=None,
        choices=["skip_batch", "rollback", "raise"],
        help="react to a NaN/Inf step cost: skip the batch, roll back to the "
             "last checkpoint with the LR halved, or raise (default: guard off)",
    )
    p.add_argument(
        "--keep_last_n", type=int, default=0,
        help="retain only the newest N pass checkpoints under --save_dir "
             "(0 = keep all)",
    )
    p.add_argument(
        "--faults", default=None,
        help="chaos-injection spec, e.g. 'feeder_raise:0.01,nan_loss:step=37' "
             "(overrides $PADDLE_TPU_FAULTS; see paddle_tpu/core/faults.py)",
    )
    p.add_argument(
        "--master_endpoints", default=None,
        help="pull training data from an elastic task master instead of the "
             "config's provider: 'host:port' or a failover list "
             "'a:p1,b:p2' (primary + standby); shards hold pickled "
             "provider-format samples",
    )
    p.add_argument(
        "--profile", default=None, metavar="pass:N",
        help="capture a jax.profiler trace of pass N and dump per-executable "
             "HLO cost analysis (top-k FLOP/byte buckets) as profile.json — "
             "the ROADMAP 'top-3 HLO cost buckets' target list. With "
             "--job=time the buckets land in the printed JSON line instead",
    )
    p.add_argument(
        "--profile_dir", default=None,
        help="where the jax.profiler trace + profile.json go "
             "(default: <save_dir>/profile, else /tmp/paddle_tpu_profile)",
    )
    p.add_argument(
        "--preempt_grace_s", type=float, default=30.0,
        help="drain budget after a SIGTERM/SIGINT preemption notice: finish "
             "the step and checkpoint within this many seconds, then exit "
             "with code 77 (preempt.EXIT_PREEMPTED) so a supervisor restart "
             "with --auto_resume=1 continues from the drained batch boundary",
    )
    p.add_argument(
        "--elastic", type=_str2bool, default=False,
        help="join the master's elastic-resize plane (needs "
             "--master_endpoints and --trainer_count > 1): a `resize` epoch "
             "announced by the master drains this trainer at a batch "
             "boundary, re-shards params/optimizer state from the canonical "
             "layout onto the new mesh data-axis size, and resumes the "
             "interrupted pass in place (see README 'Elastic resize')",
    )


# Names injected into legacy provider modules: the reference embedded
# Python 2, so providers in the wild use py2 builtins. A compat shim at module
# load is what lets those files run unmodified under py3.
_PY2_SHIMS = {"xrange": range, "unicode": str, "long": int, "basestring": str}


def _load_provider_module(name: str, config_dir: str = ""):
    """Import a provider module, preferring the config script's directory
    (PyDataProvider2.cpp loads module.obj next to the config), with py2
    builtin shims injected for legacy providers."""
    path = os.path.join(config_dir or ".", name + ".py") if name else None
    if path and os.path.exists(path):
        import importlib.util

        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        mod.__dict__.update(_PY2_SHIMS)
        sys.modules.setdefault(name, mod)
        spec.loader.exec_module(mod)
        return mod
    if config_dir and config_dir not in sys.path:
        sys.path.insert(0, config_dir)
    mod = importlib.import_module(name)
    for k, v in _PY2_SHIMS.items():
        mod.__dict__.setdefault(k, v)
    return mod


def _load_provider(dc: proto.DataConfig):
    """DataConfig → (provider, file_list, args) — the PyDataProvider2 load
    path (gserver/dataproviders/PyDataProvider2.cpp:195 loads module.obj),
    or the builtin ProtoData provider for binary shards
    (REGISTER_DATA_PROVIDER proto/proto_sequence, ProtoDataProvider.cpp:31)."""
    if (dc.type or "").startswith("proto"):
        from paddle_tpu.data.proto_data import (
            make_proto_provider, resolve_data_path,
        )

        # one provider per DataConfig: bind_provider_types and _make_reader
        # both land here, and the provider caches all decoded shards
        provider = getattr(dc, "_builtin_provider", None)
        if provider is None:
            provider = make_proto_provider(dc)
            dc._builtin_provider = provider
        files: List[str] = []
        flist = resolve_data_path(dc.files, dc.config_dir or "") or dc.files
        if flist and os.path.exists(flist):
            with open(flist) as f:
                files = [ln.strip() for ln in f if ln.strip()]
        elif flist:
            files = [flist]
        return provider, files, None
    mod = _load_provider_module(dc.load_data_module, dc.config_dir)
    provider = getattr(mod, dc.load_data_object)
    files: List[str] = []
    flist = dc.files
    if flist and not os.path.exists(flist) and dc.config_dir:
        cand = os.path.join(dc.config_dir, flist)
        if os.path.exists(cand):
            flist = cand
    if flist and os.path.exists(flist):
        with open(flist) as f:
            files = [ln.strip() for ln in f if ln.strip()]
    elif flist:
        files = [flist]
    args = json.loads(dc.load_data_args) if dc.load_data_args else None
    return provider, files, args


def bind_provider_types(topology, dc: proto.DataConfig):
    """Bind the provider's input_types to the topology's data layers — the
    runtime slot binding PyDataProvider2.cpp does. Returns a feeding map
    {layer_name: slot_index} (sample tuples arrive in slot order).

    Dict input_types bind by name. List input_types bind positionally over
    the data layers in declaration order, except when the declared sizes are
    incompatible (e.g. GoogleNet declares the label layer first while the
    provider yields (image, label)) — then slots match by kind and size the
    way DataProviderConverter reconciles Arguments."""
    provider, files, args = _load_provider(dc)
    kwargs = dict(args) if isinstance(args, dict) else {}
    settings = provider.make_settings(obj=None, file_list=files, **kwargs)
    types = settings.input_types
    if types is None:
        return None
    layers = list(topology.data_layers().values())
    # Inputs("a", "b", ...) in the config pins the slot order (the reference
    # feeds inArgs in Inputs order, not graph order — chunking.conf's label
    # slot is last by Inputs but an early cost dependency topologically)
    declared = getattr(topology, "declared_inputs", None)
    if declared:
        by_name = {l.name: l for l in layers}
        picked = [by_name[n] for n in declared if n in by_name]
        if len(picked) == len(layers):
            layers = picked

    def apply_spec(layer, spec):
        from paddle_tpu.nn.graph import record_layers
        from paddle_tpu.v2.layer import data as _v2_data

        with record_layers([]):  # shape probe only — keep out of the graph
            tmpl = _v2_data(layer.name + ".__tmpl__", spec)
        layer.data_type = spec
        layer.shape = tmpl.shape
        layer.is_seq = tmpl.is_seq

    if isinstance(types, dict):
        feeding = {}
        for i, (lname, spec) in enumerate(types.items()):
            layer = topology.data_layers().get(lname)
            if layer is None:
                raise ValueError(f"provider input_types names unknown layer {lname!r}")
            apply_spec(layer, spec)
            feeding[lname] = i
        return feeding

    types = list(types)
    if len(types) != len(layers):
        raise ValueError(
            f"provider declares {len(types)} slots but the config has "
            f"{len(layers)} data layers"
        )

    def declared_size(layer):
        size = getattr(layer, "_v1_size", None)
        if size is None and getattr(layer, "shape", None):
            size = 1
            for d in layer.shape:
                size *= int(d)
        return size

    def compatible(layer, spec) -> bool:
        if spec.kind.startswith("dense") and not isinstance(spec.dim, tuple):
            return declared_size(layer) in (None, int(spec.dim))
        return True

    order = list(layers)
    if not all(compatible(l, s) for l, s in zip(order, types)):
        # declaration order mismatches the slot order — rebind dense slots
        # to the layers whose declared size matches, then fill the rest
        remaining = list(layers)
        order = []
        for spec in types:
            pick = next((l for l in remaining if compatible(l, spec)), remaining[0])
            remaining.remove(pick)
            order.append(pick)
    for layer, spec in zip(order, types):
        apply_spec(layer, spec)
    return {layer.name: i for i, layer in enumerate(order)}


def _make_reader(dc: proto.DataConfig, batch_size: int, is_train: bool = True) -> Callable:
    provider, files, args = _load_provider(dc)
    kwargs = dict(args) if isinstance(args, dict) else {}
    # @provider batching knobs (PyDataProvider2.py): calc_batch_size gives a
    # per-sample cost (e.g. token count); can_over_batch_size controls whether
    # the overflowing sample stays in the current batch or starts the next
    calc = getattr(provider, "calc_batch_size", None)
    can_over = getattr(provider, "can_over_batch_size", True)

    def reader():
        batch: List[Any] = []
        acc = 0
        for sample in provider(
            obj=None, file_list=files or None, is_train=is_train, **kwargs
        ):
            cost = int(calc(sample)) if calc is not None else 1
            if batch and not can_over and acc + cost > batch_size:
                yield batch
                batch, acc = [], 0
            batch.append(sample)
            acc += cost
            if acc >= batch_size:
                yield batch
                batch, acc = [], 0
        if batch:
            yield batch

    return reader


def cmd_train(args: argparse.Namespace) -> int:
    use_tpu = args.use_gpu if args.use_gpu is not None else args.use_tpu
    if not use_tpu:
        # jax reads JAX_PLATFORMS when it is imported, and
        # paddle_tpu.trainer/parallel import it at module top; for an
        # in-process caller that already imported jax the env var comes too
        # late, so the config is set as well
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" in sys.modules:
            sys.modules["jax"].config.update("jax_platforms", "cpu")

    from paddle_tpu.core import init_ctx
    from paddle_tpu.config import build_optimizer, parse_config
    from paddle_tpu.metrics.evaluators import EVALUATORS
    from paddle_tpu.trainer.trainer import SGDTrainer

    init_ctx.init(
        use_tpu=use_tpu,
        trainer_count=args.trainer_count,
        log_period=args.log_period,
        seed=args.seed,
        **({"dtype_policy": args.dtype} if args.dtype else {}),
    )

    if args.faults:
        from paddle_tpu.core import faults

        faults.get().configure(args.faults)

    # SIGTERM/SIGINT (cloud preemption notice) → drain at the next batch
    # boundary, checkpoint, exit with preempt.EXIT_PREEMPTED (see below)
    from paddle_tpu.core import preempt

    preempt.install(grace_s=args.preempt_grace_s)

    pc = parse_config(args.config, args.config_args, emit_proto=False)
    oc = pc.trainer_config.opt_config
    bundle = build_optimizer(oc)

    parallel = None
    if args.trainer_count > 1:
        from paddle_tpu.parallel import DataParallel, make_mesh

        parallel = DataParallel(make_mesh({"data": args.trainer_count}))
    elif args.shard_update or args.grad_compression != "none":
        import logging

        logging.getLogger("paddle_tpu.cli").warning(
            "--shard_update/--grad_compression need --trainer_count > 1 "
            "(no data axis to shard over); ignoring them"
        )
        args.shard_update, args.grad_compression = False, "none"

    # Outputs() may mix training costs with plain fetch layers
    # (sample_trainer_config_qb_rnn.conf: Outputs("cost", "qb_rnnlast_left"));
    # only cost layers join the objective, the rest ride as extra outputs
    cost_outputs = [l for l in pc.outputs if getattr(l, "is_cost", False)]
    fetch_outputs = [l for l in pc.outputs if not getattr(l, "is_cost", False)]
    if not cost_outputs:
        cost_outputs, fetch_outputs = pc.outputs, []

    # evaluator outputs must be network outputs so the step returns them
    extra_layers, seen = list(fetch_outputs), {l.name for l in cost_outputs}
    seen |= {l.name for l in fetch_outputs}
    eval_objs = []
    net_layers = pc.topology.network.layers_by_name
    for ec in pc.context.evaluators:
        ins = [net_layers[n] for n in ec.input_layers if n in net_layers]
        for l in ins:
            if l.name not in seen:
                seen.add(l.name)
                extra_layers.append(l)
        eval_objs.append((ec, [l.name for l in ins]))

    trainer = SGDTrainer(
        cost_outputs,
        bundle.optimizer,
        extra_outputs=extra_layers,
        schedule=bundle.schedule,
        model_average=bundle.model_average,
        parallel=parallel,
        seed=args.seed,
        remat=args.remat,
        precision=args.precision,
        divergence_policy=args.divergence_policy,
        guard_check_every=args.guard_check_every,
        shard_update=args.shard_update,
        grad_compression=args.grad_compression,
    )
    batch_size = oc.batch_size or 32

    if (
        pc.trainer_config.data_config is None
        and args.job != "test"
        and not args.master_endpoints
    ):
        # --master_endpoints replaces the provider as the sample source, so a
        # config without local data sources is legitimate there
        print("config declares no data sources (define_py_data_sources2)", file=sys.stderr)
        return 2

    # bind the provider's input_types to the data layers (the runtime slot
    # binding PyDataProvider2.cpp performs) before building the feeder
    feeding = None
    bind_dc = pc.trainer_config.data_config or pc.trainer_config.test_data_config
    if bind_dc is not None:
        # hard-fail like PyDataProvider2's slot binding: a mis-bound provider
        # would otherwise train on garbage (VERDICT r2 weak #8)
        feeding = bind_provider_types(pc.topology, bind_dc)
    feeder = pc.topology.make_feeder(feeding)
    reader = (
        _make_reader(pc.trainer_config.data_config, batch_size)
        if pc.trainer_config.data_config
        else None
    )
    if args.master_endpoints:
        # elastic-cluster data path: this trainer is a stateless consumer of
        # the shared task queue; the endpoint list gives it a standby to fail
        # over to when the primary master dies mid-pass
        from paddle_tpu.data import reader as rd
        from paddle_tpu.runtime.master import cluster_reader

        reader = rd.batch(cluster_reader(args.master_endpoints), batch_size)
    test_reader = (
        _make_reader(pc.trainer_config.test_data_config, batch_size, is_train=False)
        if pc.trainer_config.test_data_config
        else None
    )

    # --profile pass:N (obs pillar 3): validate the spec up front; the
    # PassProfiler wraps the event handler to capture exactly that pass
    profiler = None
    profile_dir = None
    if args.profile:
        from paddle_tpu.obs import profile as obs_profile

        profile_dir = args.profile_dir or (
            os.path.join(args.save_dir, "profile")
            if args.save_dir
            else "/tmp/paddle_tpu_profile"
        )
        try:
            profiler = obs_profile.PassProfiler.from_spec(
                args.profile, logdir=profile_dir
            )
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2

    if args.init_model_path:
        first = next(iter(reader() if reader else test_reader()))
        batch = feeder(first)
        if parallel is not None:
            batch = parallel.shard_batch(batch)
        trainer.init_state(batch)
        trainer.load(args.init_model_path, args.start_pass - 1 if args.start_pass else None)

    if args.job == "time":
        return _job_time(
            trainer, reader, feeder, args.num_batches,
            profile=args.profile, profile_dir=profile_dir,
        )
    if args.job == "test":
        if test_reader is None:
            print("--job=test needs a test data source", file=sys.stderr)
            return 2
        res = trainer.test(test_reader, feeder)
        print(json.dumps({"test_cost": res["cost"], "samples": res["samples"]}))
        return 0

    # evaluator accumulation through the event stream (Evaluator::start/eval/
    # finish per pass, Evaluator.h:42)
    from paddle_tpu.trainer.events import BeginPass, EndIteration, EndPass

    def _make_evaluator(ec):
        kw = {}
        if ec.type == "chunk":
            kw = dict(scheme=ec.chunk_scheme or "IOB",
                      num_chunk_types=ec.num_chunk_types or 1,
                      excluded_chunk_types=ec.excluded_chunk_types)
        elif ec.type == "precision_recall":
            kw = dict(positive_label=(
                None if ec.positive_label in (-1, None) else ec.positive_label))
        elif ec.type == "max_id_printer":
            kw = dict(num_results=ec.num_results)
        elif ec.type == "seq_text_printer":
            # resolve the config's relative result/dict paths against the
            # config directory with generation.py's own helper — training
            # from another cwd must not break dict loading or scatter result
            # files. Only an explicitly configured result_file follows the
            # config dir; the fallback stays cwd-relative so a config on a
            # read-only tree still trains.
            from paddle_tpu.trainer.generation import _resolve

            base = (bind_dc.config_dir if bind_dc is not None else None) or (
                os.path.dirname(os.path.abspath(args.config))
            )
            kw = dict(
                result_file=(
                    _resolve(ec.result_file, base)
                    if ec.result_file
                    else "generated_sequences.txt"
                ),
                dict_file=_resolve(ec.dict_file, base),
                delimited=ec.delimited,
            )
        return EVALUATORS.get(ec.type)(**kw)

    active = [
        (_make_evaluator(ec), names) for ec, names in eval_objs
    ] if eval_objs else []
    if active and args.steps_per_dispatch > 1:
        # fused dispatches return no per-batch extra outputs, so evaluator
        # update() would never run — producing stats over zero samples.
        # Losing the user's requested metrics silently is worse than losing
        # the fusion win; fall back loudly.
        import logging

        logging.getLogger("paddle_tpu.cli").warning(
            "config declares %d evaluator(s), which need per-batch network "
            "outputs — --steps_per_dispatch=%d would starve them; falling "
            "back to steps_per_dispatch=1 (drop the evaluators to keep the "
            "fused dispatch)", len(active), args.steps_per_dispatch,
        )
        args.steps_per_dispatch = 1

    def handler(event):
        if isinstance(event, BeginPass):
            for ev, _ in active:
                ev.start()
        elif isinstance(event, EndIteration) and active:
            for ev, names in active:
                vals = [event.metrics.get(n) for n in names]
                if vals and vals[0] is not None:
                    kw = {"output": vals[0]}
                    if len(vals) > 1:
                        kw["label"] = vals[1]
                    if len(vals) > 2:
                        kw["weight"] = vals[2]
                    try:
                        ev.update(**kw)
                    except Exception as e:  # metric failure must not kill training
                        import logging

                        logging.getLogger("paddle_tpu.cli").warning(
                            "evaluator %s failed: %s", type(ev).__name__, e
                        )
        elif isinstance(event, EndPass):
            stats = {type(ev).__name__: ev.finish() for ev, _ in active}
            line = f"pass {event.pass_id}: avg_cost={event.metrics['avg_cost']:.6f}"
            if "test_cost" in event.metrics:
                line += f" test_cost={event.metrics['test_cost']:.6f}"
            for k, v in stats.items():
                line += f" {k}={v}"
            print(line)

    if profiler is not None:
        handler = profiler.wrap(handler)
    # the cost report lowers the step against one feed-ready batch; grab it
    # from the PRE-prefetch reader so no worker thread outlives the report
    profile_reader = reader

    if args.prefetch_depth > 0 and reader is not None:
        # run the feeder + batch sharding + H2D on a background thread so
        # host input prep overlaps the donated compiled step; with
        # --steps_per_dispatch=K the worker also stacks K batches into one
        # fused-dispatch payload (one device put per K steps)
        from paddle_tpu.data.pipeline import DevicePrefetcher

        reader = DevicePrefetcher(
            reader, feeder, parallel=parallel,
            prefetch_depth=args.prefetch_depth,
            stack_k=args.steps_per_dispatch,
        )

    from paddle_tpu.trainer.trainer import Preempted

    resize_client = None
    resize_barrier = None
    if args.elastic:
        if not args.master_endpoints or parallel is None:
            print(
                "--elastic needs --master_endpoints (the resize plane rides "
                "the master heartbeats) and --trainer_count > 1 (a mesh to "
                "re-shape); continuing without elastic resize",
                file=sys.stderr,
            )
        else:
            from paddle_tpu.runtime.master import ResizeClient

            try:
                resize_client = ResizeClient(args.master_endpoints)
                resize_barrier = resize_client.barrier
            except ConnectionError as e:
                # same degrade contract as the misconfiguration branch
                # above: an unreachable master must not abort training (a
                # supervisor loop with --auto_resume restarts into the
                # current mesh and re-attaches when the master returns)
                print(
                    f"--elastic: master unreachable ({e}); continuing "
                    "without elastic resize",
                    file=sys.stderr,
                )

    try:
        trainer.train(
            reader,
            num_passes=args.num_passes,
            event_handler=handler,
            feeder=feeder,
            test_reader=test_reader,
            save_dir=args.save_dir,
            log_period=args.log_period,
            auto_resume=args.auto_resume,
            keep_last_n=args.keep_last_n or None,
            steps_per_dispatch=args.steps_per_dispatch,
            async_checkpoint=args.async_checkpoint,
            resize_barrier=resize_barrier,
        )
    except Preempted as p:
        # distinct exit code: a supervisor restarting with --auto_resume=1
        # continues bitwise-identically from the drained batch boundary
        where = (
            f"checkpoint saved to {p.checkpoint_dir}"
            if p.checkpoint_dir
            else "no mid-pass checkpoint (no --save_dir or grace expired)"
        )
        print(
            f"preempted ({p.reason}): drained at pass {p.pass_id} batch "
            f"{p.batches_done}; {where}; restart with --auto_resume=1 to "
            f"continue", file=sys.stderr,
        )
        return preempt.EXIT_PREEMPTED
    finally:
        if resize_client is not None:
            resize_client.close()

    if profiler is not None:
        from paddle_tpu.obs import profile as obs_profile

        report = {
            "profile": args.profile,
            "trace_dir": profile_dir,
            "captured": profiler.captured,
        }
        try:
            raw = (
                next(iter(profile_reader()), None)
                if profile_reader is not None
                else None
            )
            if raw is not None and trainer.state is not None:
                batch = (
                    feeder(raw)
                    if feeder is not None and not isinstance(raw, dict)
                    else raw
                )
                if parallel is not None:
                    batch = parallel.shard_batch(batch)
                report.update(obs_profile.trainer_cost_report(trainer, batch))
        except Exception as e:  # the report must not fail a finished run
            import logging

            logging.getLogger("paddle_tpu.cli").warning(
                "HLO cost report failed: %r", e
            )
            report["error"] = repr(e)[-400:]
        path = obs_profile.write_report(
            report, os.path.join(profile_dir, "profile.json")
        )
        print(json.dumps({"profile_json": path,
                          "trace_dir": profile_dir if profiler.captured else None}))
    return 0


def _job_time(
    trainer, reader, feeder, num_batches: int,
    profile: Optional[str] = None, profile_dir: Optional[str] = None,
) -> int:
    """--job=time (TrainerBenchmark.cpp): time num_batches hot-loop batches.
    With --profile, the timed window is captured as a jax.profiler trace and
    the step's top-k HLO cost buckets join the printed bench JSON line."""
    import jax

    it = iter(reader())
    batches = []
    for _ in range(num_batches):
        try:
            batches.append(feeder(next(it)))
        except StopIteration:
            break
    if not batches:
        print("no data", file=sys.stderr)
        return 2
    if trainer.parallel is not None:
        batches = [trainer.parallel.shard_batch(b) for b in batches]
    trainer.init_state(batches[0])
    step = trainer._make_step()
    state = trainer.state
    lowered = None
    if profile:
        # lower BEFORE the donated executions below delete the state buffers;
        # AOT compile for the cost report happens after timing
        lowered = step.lower(state, batches[0])
    state, cost, _ = step(state, batches[0])  # compile
    jax.block_until_ready(cost)
    if profile:
        from paddle_tpu.core import stats as _stats

        _stats.profiler_start(profile_dir or "/tmp/paddle_tpu_profile")
    t0 = time.time()
    for b in batches:
        state, cost, _ = step(state, b)
    jax.block_until_ready(cost)
    dt = (time.time() - t0) / len(batches)
    out = {"ms_per_batch": dt * 1e3, "batches": len(batches)}
    if profile:
        from paddle_tpu.core import stats as _stats
        from paddle_tpu.obs import profile as obs_profile

        _stats.profiler_stop()
        out["trace_dir"] = profile_dir or "/tmp/paddle_tpu_profile"
        try:
            out["hlo_cost"] = obs_profile.compiled_cost_report(
                lowered.compile()
            )
        except Exception as e:  # the timing line must survive a backend
            # that cannot cost-analyze
            out["hlo_cost_error"] = repr(e)[-300:]
    print(json.dumps(out))
    return 0


def cmd_dump_config(args: argparse.Namespace) -> int:
    from paddle_tpu.config import parse_config

    pc = parse_config(args.config, args.config_args)
    sys.stdout.write(proto.to_text(pc.trainer_config))
    return 0


def cmd_merge_model(args: argparse.Namespace) -> int:
    from paddle_tpu.capi.merge_model import merge_model

    out = merge_model(args.config, args.model_dir, args.output, args.config_args)
    print(out)
    return 0


def _serve_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0, help="0 picks a free port")
    p.add_argument(
        "--demo", action="store_true",
        help="serve the built-in seeded demo LM (smoke/bench mode)",
    )
    p.add_argument("--load", default=None, help="ServableLM .npz to serve")
    p.add_argument(
        "--config", default=None,
        help="v1 config script: serve whole-request generation through a "
             "long-lived GenerationSession (RPC method generate_config)",
    )
    p.add_argument("--model_dir", default=None, help="params for --config")
    p.add_argument("--config_args", default="")
    p.add_argument("--max_slots", type=int, default=8,
                   help="concurrent decode slots = the continuous batch width")
    p.add_argument("--page_size", type=int, default=16,
                   help="tokens per KV page")
    p.add_argument("--num_pages", type=int, default=0,
                   help="KV page pool size (0 = worst case for max_slots)")
    p.add_argument("--prefill_buckets", default="16,32,64",
                   help="padded prompt lengths; one prefill compile each")
    p.add_argument("--prefill_chunk", type=int, default=0,
                   help="chunked prefill (0 = off): prompts longer than this "
                        "commit their KV one C-token chunk per engine step, "
                        "interleaved with decode, so a long prompt joining "
                        "mid-stream never stalls running streams' inter-token "
                        "latency; also lifts the bucket cap on prompt length "
                        "(any prompt up to the model's max_len is admissible)")
    p.add_argument("--speculate_k", type=int, default=0,
                   help="prompt-lookup speculative decoding (0 = off): draft "
                        "up to K continuation tokens per request per step "
                        "from the request's own committed n-grams and score "
                        "them all in ONE fixed-shape [1,K+1] verify call — "
                        "the matched prefix commits, the first divergent "
                        "token comes free from the verify logits, so "
                        "high-overlap streams advance several tokens per "
                        "step; tokens are identical to --speculate_k 0 "
                        "(greedy AND seeded sampling: acceptance replays "
                        "through the per-emitted-token key fold)")
    p.add_argument("--prefix_cache", action="store_true",
                   help="shared-prefix KV cache (needs --prefill_chunk): "
                        "committed prompt pages index by tenant-namespaced "
                        "token hash at page granularity; a new request "
                        "aliases its cached prefix pages read-only "
                        "(refcounted, copy-on-write at the first divergent "
                        "page) and prefills only its own suffix — tokens "
                        "stay bitwise-identical to cache-off, TTFT drops by "
                        "the shared fraction")
    p.add_argument("--prefix_cache_pages", type=int, default=0,
                   help="cap on cached prefix pages (0 = bounded only by "
                        "the pool; unreferenced cached pages LRU-evict "
                        "under pool pressure either way)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="default sampling temperature for requests that do "
                        "not set one (0 = greedy argmax); sampling is "
                        "on-device through a per-request seeded key, so "
                        "engine-crash replay regenerates identical tokens")
    p.add_argument("--top_k", type=int, default=0,
                   help="default top-k truncation for requests that do not "
                        "set one (0 = off)")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel size (0/1 = single chip): shard "
                        "params and the KV page pool over the mesh 'model' "
                        "axis via the named sharding rules "
                        "(parallel/rules.py); needs n_heads and vocab "
                        "divisible by N, and N devices visible; tokens are "
                        "identical to single-chip serving")
    p.add_argument("--max_new_limit", type=int, default=64)
    p.add_argument("--max_queue", type=int, default=256)
    p.add_argument("--tenant_tokens", type=float, default=0.0,
                   help="per-tenant token-bucket capacity (0 = unlimited)")
    p.add_argument("--tenant_tokens_per_s", type=float, default=0.0)
    p.add_argument("--tenant_concurrent", type=int, default=0,
                   help="per-tenant concurrent-request cap (0 = unlimited)")
    p.add_argument("--default_deadline_s", type=float, default=0.0,
                   help="total-latency deadline for requests that do not set "
                        "one (0 = none): expired requests are cancelled with "
                        "reason 'deadline' and their KV pages recycled; also "
                        "arms load-aware shedding (doomed requests rejected "
                        "at admission with retry_after_ms)")
    p.add_argument("--default_ttft_deadline_s", type=float, default=0.0,
                   help="time-to-first-token deadline default (0 = none); "
                        "misses are counted (the client-hedging signal), "
                        "not fatal")
    p.add_argument("--engine_restart_max", type=int, default=3,
                   help="engine crash/stall recoveries before the server "
                        "gives up and fails outstanding requests "
                        "('engine_error')")
    p.add_argument("--engine_stall_timeout_s", type=float, default=10.0,
                   help="supervisor stall watchdog: no decode-step progress "
                        "for this long with work pending restarts the engine")
    p.add_argument("--lease_s", type=float, default=30.0,
                   help="tenant lease; silent clients are evicted and their "
                        "queued requests cancelled")
    p.add_argument("--require_register", type=_str2bool, default=False,
                   help="reject requests without a registered tenant lease")
    p.add_argument(
        "--master_endpoints", default=None,
        help="routing master to health-check: its snapshot_failures / lease "
             "evictions / live+evicted trainer counts are forwarded in this "
             "server's stats() so deployments see control-plane degradation",
    )
    p.add_argument(
        "--router_endpoints", default=None,
        help="join a serving-router fleet (ISSUE 15) as a replica: register "
             "this server's endpoint with the router at host:port and renew "
             "the lease with load-snapshot heartbeats; a wedged engine "
             "self-fences so the router fails in-flight work over to a "
             "survivor. Pass a comma-separated primary,standby list "
             "(ISSUE 18): after consecutive heartbeat connection failures "
             "the agent rotates to the standby router and re-registers, "
             "whose takeover sweep re-adopts this replica's in-flight work",
    )
    p.add_argument(
        "--advertise_host", default=None,
        help="hostname the router should dial this replica back on "
             "(defaults to --host; set it when serving behind NAT/containers)",
    )
    p.add_argument("--stall_fence_s", type=float, default=5.0,
                   help="replica self-fence: with work pending and no engine "
                        "progress for this long (between steps), heartbeats "
                        "to the router stop so its lease can lapse")
    p.add_argument("--exit_on_drain", action="store_true",
                   help="exit cleanly when a router-ordered planned drain "
                        "completes (the autoscaler's spawn/drain replica "
                        "lifecycle, ISSUE 17)")
    # demo model shape knobs (ignored with --load)
    p.add_argument("--max_len", type=int, default=0,
                   help="demo model position-embedding capacity (0 = largest "
                        "bucket + max_new_limit); raise it with "
                        "--prefill_chunk so chunked prefill has headroom for "
                        "prompts beyond the buckets")
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--n_layers", type=int, default=2)
    p.add_argument("--d_model", type=int, default=32)
    p.add_argument("--n_heads", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)


def build_serve_session(args: argparse.Namespace, quotas=None):
    """The ServingSession `serve --demo` / `serve --load` runs, from parsed
    `_serve_args` flags (chip_smoke.py builds its sessions through here, so
    the smoke serves exactly what the CLI serves)."""
    from paddle_tpu.core.init_ctx import enable_compilation_cache
    from paddle_tpu.serving.session import ServingSession, make_demo_session

    enable_compilation_cache()
    buckets = tuple(
        int(b) for b in args.prefill_buckets.split(",") if b.strip()
    )
    session_kw = dict(
        max_slots=args.max_slots,
        page_size=args.page_size,
        num_pages=args.num_pages or None,
        prefill_buckets=buckets,
        prefill_chunk=args.prefill_chunk or None,
        prefix_cache=args.prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages or None,
        speculate_k=args.speculate_k,
        default_temperature=args.temperature,
        default_top_k=args.top_k,
        max_new_limit=args.max_new_limit,
        max_queue=args.max_queue,
        quotas=quotas,
        default_deadline_s=args.default_deadline_s or None,
        default_ttft_deadline_s=args.default_ttft_deadline_s or None,
        engine_restart_max=args.engine_restart_max,
        engine_stall_timeout_s=args.engine_stall_timeout_s,
    )
    if args.load:
        # the checkpoint records its architecture (ServableLM or LoopedLM)
        from paddle_tpu.serving.looped_lm import load_checkpoint

        mesh = None
        if args.tp and args.tp > 1:
            from paddle_tpu.parallel.rules import make_tp_mesh

            mesh = make_tp_mesh(args.tp)
        model, params = load_checkpoint(args.load, mesh=mesh)
        return ServingSession(model, params, **session_kw)
    return make_demo_session(
        vocab=args.vocab, n_layers=args.n_layers,
        d_model=args.d_model, n_heads=args.n_heads, seed=args.seed,
        max_len=args.max_len or None, tp=args.tp,
        **session_kw,
    )


def cmd_serve(args: argparse.Namespace) -> int:
    """Long-lived serving process: load once, serve until SIGTERM/SIGINT."""
    import signal as _signal
    import threading

    from paddle_tpu.serving.quota import TenantQuotas
    from paddle_tpu.serving.server import ServingServer

    quotas = None
    if args.tenant_tokens_per_s > 0 and args.tenant_tokens <= 0:
        # a refill rate without a bucket capacity is a no-op; saying nothing
        # would leave the operator believing rate limiting is on
        print(
            "--tenant_tokens_per_s needs --tenant_tokens (the bucket "
            "capacity); no token quota will be enforced", file=sys.stderr,
        )
    elif args.tenant_tokens > 0 and args.tenant_tokens_per_s <= 0:
        # the inverse surprise: a bucket that never refills is a LIFETIME
        # cap, not the documented rate limit — permanent lockout once drained
        print(
            "--tenant_tokens without --tenant_tokens_per_s never refills: "
            "each tenant gets a one-time lifetime budget of "
            f"{args.tenant_tokens:.0f} tokens", file=sys.stderr,
        )
    if args.tenant_tokens > 0 or args.tenant_concurrent > 0:
        quotas = TenantQuotas(
            token_capacity=args.tenant_tokens or None,
            tokens_per_s=args.tenant_tokens_per_s,
            max_concurrent=args.tenant_concurrent or None,
        )

    session = None
    if args.demo or args.load:
        session = build_serve_session(args, quotas)

    gen_session = None
    if args.config:
        from paddle_tpu.config import parse_config
        from paddle_tpu.trainer.generation import GenerationSession

        pc = parse_config(args.config, args.config_args, emit_proto=False)
        gen_session = GenerationSession(
            pc, model_dir=args.model_dir,
            base_dir=os.path.dirname(os.path.abspath(args.config)),
        )

    if session is None and gen_session is None:
        print(
            "serve needs a model: --demo, --load=model.npz, or "
            "--config=conf.py [--model_dir=DIR]", file=sys.stderr,
        )
        return 2

    stop_evt = threading.Event()
    server = ServingServer(
        session=session, gen_session=gen_session,
        host=args.host, port=args.port, lease_s=args.lease_s,
        require_register=args.require_register,
        master_endpoints=args.master_endpoints,
        router_endpoints=args.router_endpoints,
        advertise_host=args.advertise_host,
        stall_fence_s=args.stall_fence_s,
        # autoscaler spawn/drain lifecycle (ISSUE 17): a router-ordered
        # drain completing shuts this process down cleanly, releasing the
        # chip the controller reclaimed
        on_drained=(stop_evt.set if args.exit_on_drain else None),
    ).start()
    _signal.signal(_signal.SIGTERM, lambda *_: stop_evt.set())
    _signal.signal(_signal.SIGINT, lambda *_: stop_evt.set())
    print(json.dumps({"role": "serve", "address": list(server.address)}),
          flush=True)
    stop_evt.wait()
    server.stop()
    if session is not None:
        print(json.dumps({"final_stats": session.stats()}), flush=True)
    return 0


def cmd_version(_args: argparse.Namespace) -> int:
    from paddle_tpu import __version__

    print(f"paddle-tpu {__version__}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="paddle_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_train = sub.add_parser("train", help="train/test/benchmark a config")
    _train_args(p_train)
    p_train.set_defaults(fn=cmd_train)

    p_dump = sub.add_parser("dump_config", help="print TrainerConfig text")
    p_dump.add_argument("--config", required=True)
    p_dump.add_argument("--config_args", default="")
    p_dump.set_defaults(fn=cmd_dump_config)

    p_merge = sub.add_parser("merge_model", help="fold config+params into one file")
    p_merge.add_argument("--config", required=True)
    p_merge.add_argument("--model_dir", required=True)
    p_merge.add_argument("--output", required=True)
    p_merge.add_argument("--config_args", default="")
    p_merge.set_defaults(fn=cmd_merge_model)

    p_serve = sub.add_parser(
        "serve", help="continuous-batching inference server"
    )
    _serve_args(p_serve)
    p_serve.set_defaults(fn=cmd_serve)

    p_ver = sub.add_parser("version")
    p_ver.set_defaults(fn=cmd_version)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
