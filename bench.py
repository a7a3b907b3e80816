"""Benchmark driver: ResNet-50 ImageNet training throughput on the TPU (the
BASELINE.json north-star metric: images/sec/chip and MFU vs the ≥50% target),
plus the seq2seq, serving and control-plane side legs.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
vs_baseline = achieved_MFU / 0.50 (the north-star MFU target), so 1.0 means
"hit the 50%-MFU goal"; extra keys are informational.

A measurement needs the chip: a default backend that is not a TPU, a TPU whose
device_kind has no entry in the peak table, or a leg that raises ends the run
with a non-zero exit code and no JSON line. Everything runs in this one
process — a chip belongs to one process at a time.
"""

from __future__ import annotations

import json
import os
import sys


def _emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def peak_tflops(device) -> float:
    """Per-chip bf16 peak TFLOP/s from device_kind (ADVICE r2: a hardcoded
    v5e denominator makes MFU untrustworthy on other generations). A kind
    that is not in the table is an error, never a default."""
    kind = device.device_kind.lower()
    table = [
        # v5e: the r3 xplane trace plane reports 202.7 peak TFLOP/s for this
        # chip; use the measured plane value as the MFU denominator rather
        # than the 197 datasheet figure
        ("v5 lite", 202.7),
        ("v5e", 202.7),
        ("v5p", 459.0),
        ("v6 lite", 918.0),  # v6e / Trillium
        ("v6e", 918.0),
        ("v4", 275.0),
        ("v3", 123.0),
        ("v2", 46.0),
    ]
    for frag, tf in table:
        if frag in kind:
            return tf
    raise RuntimeError(
        f"no peak TFLOP/s entry for device_kind {device.device_kind!r}: add "
        "it to bench.peak_tflops with its source"
    )


def run_seq2seq(peak: float) -> dict:
    """Seq2seq NMT with attention (BASELINE config #3): teacher-forced
    training tokens/sec/chip on the reference demo's model scale (wmt14
    vocab 30k, embed/hidden 512 — train.conf of demo/seqToseq).

    ISSUE 9 (the MFU push): the metric now times TWO legs at the SAME
    shapes — the bf16 mixed-precision step (the headline, MXU-native) and
    the f32 baseline — both platform-tagged, with each leg's top-3 HLO cost
    buckets. The >=2x gate (speedup_vs_f32) is structural to the MXU: f32
    dots at Precision.HIGHEST cost ~6 bf16 MXU passes, so bf16 wins big on
    TPU."""
    import jax
    import numpy as np

    from paddle_tpu.models import Seq2SeqModel
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.core.benchmark import time_train_steps

    vocab = int(os.environ.get("BENCH_S2S_VOCAB", "30000"))
    dim = int(os.environ.get("BENCH_S2S_DIM", "512"))
    # "auto": quick-sweep candidate batch sizes on the chip and keep the
    # best tokens/s (r3's optimum was 128; the r4 decoder hoist + fused
    # xent shift the balance toward larger batches — measure, don't guess)
    bs_spec = os.environ.get("BENCH_S2S_BATCH", "auto")
    src_len = trg_len = int(os.environ.get("BENCH_S2S_LEN", "50"))
    steps = max(1, int(os.environ.get("BENCH_S2S_STEPS", "16")))
    warmup = 2
    # the per-leg top-3 hlo_cost buckets are the profile-driven pass's
    # artifact. BENCH_PROFILE=0 opts out (saves one AOT compile per leg).
    profile_on = os.environ.get("BENCH_PROFILE", "1") == "1"

    def make_step_for(bs: int, precision: str):
        reset_name_scope()
        model = Seq2SeqModel(vocab, vocab, embed_dim=dim, hidden_dim=dim)
        trainer = SGDTrainer(
            model.cost, Adam(learning_rate=1e-3), precision=precision
        )
        rs = np.random.RandomState(0)
        batch = {
            "source_ids": rs.randint(2, vocab, (bs, src_len)).astype(np.int32),
            "source_ids.lengths": np.full(bs, src_len, np.int32),
            "target_ids": rs.randint(2, vocab, (bs, trg_len)).astype(np.int32),
            "target_ids.lengths": np.full(bs, trg_len, np.int32),
            "label_ids": rs.randint(2, vocab, (bs, trg_len)).astype(np.int32),
            "label_ids.lengths": np.full(bs, trg_len, np.int32),
        }
        batch = jax.device_put(batch)
        trainer.init_state(batch)
        return trainer, trainer._make_step(), batch

    sweep_info = {}
    if bs_spec == "auto":
        candidates = [128, 256, 512]
        rates = {}
        for cand in candidates:
            try:
                tr, stp, bt = make_step_for(cand, "bf16")
                sec, _ = time_train_steps(stp, tr.state, bt, steps=3, warmup=1)
                rates[cand] = cand * trg_len / sec
            except Exception as exc:  # noqa: BLE001 — OOM etc: skip candidate
                sys.stderr.write(f"[bench] s2s bs={cand} failed: {exc!r}\n")
        bs = max(rates, key=rates.get) if rates else 128
        sweep_info = {
            "batch_sweep_tokens_per_sec": {
                str(k): round(v, 0) for k, v in rates.items()
            }
        }
        sys.stderr.write(f"[bench] s2s batch sweep: {rates} -> {bs}\n")
    else:
        bs = int(bs_spec)

    # Matmul FLOPs per target token (MACs x2), training ~= 3x forward.
    # Encoder work is amortized per target token (src_len == trg_len here).
    E = H = dim
    enc = 2 * 3 * (E * H + H * H) * 2            # bi-GRU, both directions
    dec = 3 * ((E + 2 * H) * H + H * H) * 2      # attention-GRU (ctx is 2H)
    attn = src_len * (2 * H) * 2                 # scores + context per token
    out = H * vocab * 2                          # output projection (dominant)
    flops_per_token = 3 * (enc + dec + attn + out)

    def time_leg(precision: str) -> dict:
        trainer, step, batch = make_step_for(bs, precision)
        lowered = step.lower(trainer.state, batch) if profile_on else None
        sec_per_step, _ = time_train_steps(
            step, trainer.state, batch, steps=steps, warmup=warmup
        )
        # the seq2seq trainer runs unsharded on one device — per-chip is per
        # this one chip regardless of how many devices the host exposes
        tokens = bs * trg_len / sec_per_step
        leg = {
            "precision": precision,
            "tokens_per_sec_per_chip": round(tokens, 1),
            "mfu": round(tokens * flops_per_token / (peak * 1e12), 4),
            "ms_per_step": round(sec_per_step * 1000, 2),
            "platform": jax.devices()[0].platform,
        }
        if lowered is not None:
            # the profile-driven pass's target list: top-3 FLOP/byte buckets
            # of exactly the executable this leg timed
            try:
                from paddle_tpu.obs.profile import compiled_cost_report

                leg["hlo_cost"] = compiled_cost_report(
                    lowered.compile(), top_k=3
                )
            except Exception as exc:  # noqa: BLE001 — never kill the leg
                leg["hlo_cost_error"] = repr(exc)[-200:]
        return leg

    bf16 = time_leg("bf16")
    # The baseline leg is best-effort: the batch size was swept under bf16
    # activations, so the f32 leg can OOM where bf16 fit — that must not
    # discard the already-measured headline, only the comparison.
    try:
        f32 = time_leg("f32")
    except Exception as exc:  # noqa: BLE001 — keep the bf16 headline
        sys.stderr.write(f"[bench] s2s f32 baseline leg failed: {exc!r}\n")
        f32 = {"precision": "f32", "error": repr(exc)[-300:]}
    # null (not 0.0) when the baseline leg failed: an unmeasured ratio must
    # stay machine-distinguishable from a measured one
    speedup = (
        round(bf16["tokens_per_sec_per_chip"] / f32["tokens_per_sec_per_chip"], 3)
        if f32.get("tokens_per_sec_per_chip")
        else None
    )
    entry = {
        "metric": "seq2seq_nmt_tokens_per_sec_per_chip",
        # headline stays the bf16 leg, the policy every earlier round measured
        "value": bf16["tokens_per_sec_per_chip"],
        "unit": "tokens/sec/chip",
        "precision": "bf16",
        "mfu": bf16["mfu"],
        "vs_baseline": round(bf16["mfu"] / 0.50, 4),
        "platform": bf16["platform"],
        "peak_tflops_bf16": peak,
        "batch_size": bs,
        "seq_len": src_len,
        "vocab": vocab,
        "hidden": dim,
        "ms_per_step": bf16["ms_per_step"],
        # the fixed-shape f32 baseline leg (same batch/seq/model), and the
        # ISSUE 9 gate ratio: >=2x expected on the MXU path
        "f32_baseline": f32,
        "speedup_vs_f32": speedup,
        **sweep_info,
    }
    if "hlo_cost" in bf16:
        entry["hlo_cost"] = dict(bf16["hlo_cost"], executable="s2s_step_bf16")
    return entry


def run_serving() -> dict:
    """Continuous-batching serving leg (ISSUE 6): tokens/sec at 16 concurrent
    streams + speedup over the sequential per-request baseline, p50/p99
    request latency, and the zero-recompile gate over a mixed-length stream.
    Small demo-LM shapes — the number tracked across rounds is the *batching*
    speedup and the latency distribution, not model FLOPs (see
    benchmarks/serving_bench.py for the full grid)."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "24"))
    max_new = int(os.environ.get("BENCH_SERVE_MAX_NEW", "16"))

    def fresh_session():
        return make_demo_session(
            vocab=256, n_layers=2, d_model=64, n_heads=2, seed=0,
            max_slots=16, page_size=16, prefill_buckets=(16, 32),
            max_new_limit=max_new,
        )

    prompts = make_prompts(
        requests, lengths=(5, 11, 16, 23, 32), vocab=256, bos_id=1, seed=0
    )
    warm_prompts = make_prompts(2, lengths=(16, 32), vocab=256, bos_id=1, seed=7)

    def measure(concurrency):
        session = fresh_session()
        run_closed_loop(session, warm_prompts, max_new, concurrency=2)
        sigs0 = session.decode_shape_signatures()
        res = run_closed_loop(session, prompts, max_new, concurrency)
        res["decode_recompiles_after_warmup"] = (
            session.decode_shape_signatures() - sigs0
        )
        return res

    seq = measure(1)
    bat = measure(16)
    speedup = (
        round(bat["tokens_per_sec"] / seq["tokens_per_sec"], 2)
        if seq["tokens_per_sec"]
        else 0.0
    )

    # chunked-prefill ITL column (ISSUE 11): the same 16-stream run with
    # long prompts joining mid-stream, chunked — p99 inter-token latency is
    # the no-stall number the serving_bench mixed-length leg gates at 0.5x
    # of the whole-prompt baseline; here the chunked leg alone rides the
    # cross-round metric (cheap), the full A/B lives in serving_bench
    from paddle_tpu.serving.workload import make_mixed_prompts

    chunk_session = make_demo_session(
        vocab=256, n_layers=2, d_model=64, n_heads=2, seed=0,
        max_slots=16, page_size=16, prefill_buckets=(16, 32),
        max_new_limit=max_new, max_len=96 + max_new, prefill_chunk=16,
    )
    run_closed_loop(chunk_session, warm_prompts, max_new, concurrency=2)
    mixed = make_mixed_prompts(
        requests, short_lengths=(5, 11, 16), long_len=96, long_every=8,
        burst=2, vocab=256, bos_id=1, seed=1,
    )
    chunks_before = chunk_session.prefill_chunks_committed  # warmup's chunks
    chunk_res = run_closed_loop(chunk_session, mixed, max_new, concurrency=16)

    return {
        "metric": "serving_tokens_per_sec_16_streams",
        "value": bat["tokens_per_sec"],
        "unit": "tokens/sec",
        # the cross-round headline: batching win over per-request serving
        "vs_baseline": speedup,
        "speedup_vs_sequential": speedup,
        "platform": jax.devices()[0].platform,
        "p50_latency_ms": bat["p50_latency_ms"],
        "p99_latency_ms": bat["p99_latency_ms"],
        "p99_inter_token_ms": bat["p99_inter_token_ms"],
        "mixed_chunked_p99_inter_token_ms": chunk_res["p99_inter_token_ms"],
        "mixed_chunked_prefill_chunks":
            chunk_session.prefill_chunks_committed - chunks_before,
        "sequential_tokens_per_sec": seq["tokens_per_sec"],
        "sequential_p50_latency_ms": seq["p50_latency_ms"],
        "decode_recompiles_after_warmup": bat["decode_recompiles_after_warmup"],
        "requests": requests,
        "max_new_tokens": max_new,
    }


def run_serving_speculative() -> list:
    """Speculative-decoding leg (ISSUE 16): ONE stream — the case batching
    cannot speed up — over high-overlap repeated-motif prompts, speculate_k
    on vs off over identical geometry. Two cross-round metrics ride out:
    `serving_single_stream_tokens_per_sec` (with the speedup-vs-non-
    speculative column) and `serving_spec_acceptance_rate` (drafted tokens
    the verify pass accepted — the workload-dependent number the speedup is
    a function of). The full gated A/B lives in benchmarks/serving_bench.py;
    this is the cheap tracked slice."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import (
        make_prompts, make_repetitive_prompts, run_closed_loop,
    )

    vocab = int(os.environ.get("BENCH_SPEC_VOCAB", "32"))
    k = int(os.environ.get("BENCH_SPEC_K", "8"))
    max_new = int(os.environ.get("BENCH_SPEC_MAX_NEW", "64"))
    requests = int(os.environ.get("BENCH_SPEC_REQUESTS", "8"))
    prompts = make_repetitive_prompts(
        requests, motif_len=4, repeats=6, vocab=vocab, bos_id=1, seed=3,
    )
    warm = make_prompts(2, lengths=(16, 32), vocab=vocab, bos_id=1, seed=7)
    warm += make_repetitive_prompts(
        1, motif_len=4, repeats=6, vocab=vocab, bos_id=1, seed=11,
    )

    def measure(speculate_k):
        session = make_demo_session(
            vocab=vocab, n_layers=2, d_model=64, n_heads=2, seed=0,
            max_slots=4, page_size=16, prefill_buckets=(16, 32),
            max_new_limit=max_new, speculate_k=speculate_k,
        )
        run_closed_loop(session, warm, max_new, concurrency=len(warm))
        res = run_closed_loop(session, prompts, max_new, concurrency=1)
        return res, session.stats()

    base, _ = measure(0)
    spec, st = measure(k)
    speedup = (
        round(spec["tokens_per_sec"] / base["tokens_per_sec"], 2)
        if base["tokens_per_sec"] else 0.0
    )
    platform = jax.devices()[0].platform
    return [
        {
            "metric": "serving_single_stream_tokens_per_sec",
            "value": spec["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": speedup,
            "speedup_vs_non_speculative": speedup,
            "non_speculative_tokens_per_sec": base["tokens_per_sec"],
            "speculate_k": k,
            "platform": platform,
            "requests": requests,
            "max_new_tokens": max_new,
        },
        {
            "metric": "serving_spec_acceptance_rate",
            "value": st["spec_acceptance_rate"],
            "unit": "accepted/drafted",
            "spec_rounds": st["spec_rounds"],
            "spec_tokens_drafted": st["spec_tokens_drafted"],
            "verify_shape_signatures": st["verify_shape_signatures"],
            "platform": platform,
        },
    ]


def run_serving_tp() -> dict:
    """Tensor-parallel serving leg (ISSUE 12): the SAME demo-LM geometry
    served single-chip and at TP=N (N = 4 when the host exposes >= 4
    devices), with per-chip param/KV-pool bytes read from sharding
    metadata. The cross-round headline is `serving_tp4_pool_bytes_per_chip`
    — the number that must keep dropping as the pool shards wider. Tokens
    must be identical across the legs (TP is result-invisible). Needs a
    host with >= 4 chips; run_bench leaves the leg out on a smaller one."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    n_dev = len(jax.devices())
    tp = 4
    if n_dev < 4:
        # never measure a DIFFERENT tp under the tp4-named headline: the
        # cross-round series would silently change scale with the host's
        # device count
        raise RuntimeError(
            f"serving TP leg needs >= 4 devices for the tp4 headline; host "
            f"exposes {n_dev}"
        )
    requests = int(os.environ.get("BENCH_SERVE_TP_REQUESTS", "16"))
    max_new = int(os.environ.get("BENCH_SERVE_MAX_NEW", "16"))
    prompts = make_prompts(
        requests, lengths=(5, 11, 16, 23, 32), vocab=256, bos_id=1, seed=0
    )
    warm = make_prompts(2, lengths=(16, 32), vocab=256, bos_id=1, seed=7)

    def leg(tp_n):
        session = make_demo_session(
            vocab=256, n_layers=2, d_model=64, n_heads=4, seed=0,
            max_slots=16, page_size=16, prefill_buckets=(16, 32),
            max_new_limit=max_new, tp=tp_n,
        )
        run_closed_loop(session, warm, max_new, concurrency=2)
        res = run_closed_loop(session, prompts, max_new, concurrency=16)
        return res, session.stats()

    base_res, base_st = leg(0)
    tp_res, tp_st = leg(tp)
    return {
        "metric": "serving_tp4_pool_bytes_per_chip",
        "value": tp_st["pool_bytes_per_chip"],
        "unit": "bytes",
        "platform": jax.devices()[0].platform,
        "tp": tp,
        "pool_bytes_per_chip_single": base_st["pool_bytes_per_chip"],
        "pool_bytes_ratio": round(
            base_st["pool_bytes_per_chip"]
            / max(tp_st["pool_bytes_per_chip"], 1), 2
        ),
        "param_bytes_per_chip": tp_st["param_bytes_per_chip"],
        "param_bytes_per_chip_single": base_st["param_bytes_per_chip"],
        "tokens_per_sec": tp_res["tokens_per_sec"],
        "tokens_per_sec_single": base_res["tokens_per_sec"],
        "p99_inter_token_ms": tp_res["p99_inter_token_ms"],
        "tp_tokens_identical": bool(
            tp_res["results"] == base_res["results"]
        ),
        "decode_shape_signatures": tp_st["decode_shape_signatures"],
    }


def run_control_plane() -> list:
    """Binary control-plane legs (ISSUE 20): the framed wire's two headline
    numbers as cross-round metrics. `control_plane_tasks_per_sec` drains a
    task ledger through a simulated trainer fleet over the framed wire
    (bulk leases + piggybacked acks; the line-JSON leg rides along as the
    round-trip denominator). `stream_bytes_per_token` is the binary push
    stream's bytes per delivered token at fan-out, with the JSON wire's
    number and the ratio alongside. Both run the REAL TCP protocol against
    in-process servers — host-side numbers, so the jax platform tag marks
    the round, not the transport. The full gated grids live in
    benchmarks/chaos_bench.py --mode fleet and benchmarks/serving_bench.py
    streaming."""
    import argparse
    import importlib.util

    import jax

    bench_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"
    )

    def load(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(bench_dir, name + ".py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    platform = jax.devices()[0].platform
    entries = []

    fleet = load("chaos_bench").run_fleet(argparse.Namespace(
        fleet_trainers=int(os.environ.get("BENCH_FLEET_TRAINERS", "24")),
        fleet_tasks=int(os.environ.get("BENCH_FLEET_TASKS", "240")),
        fleet_lease_batch=8,
        seed=0,
    ))
    entries.append({
        "metric": "control_plane_tasks_per_sec",
        "value": fleet["value"],
        "unit": fleet["unit"],
        "round_trip_reduction": fleet["round_trip_reduction"],
        "round_trips_per_task": fleet["framed"]["round_trips_per_task"],
        "round_trips_per_task_json": fleet["legacy"]["round_trips_per_task"],
        "bytes_per_task": fleet["framed"]["bytes_per_task"],
        "trainers": fleet["framed"]["trainers"],
        "lease_batch": fleet["lease_batch"],
        "exactly_once": fleet["gates"]["exactly_once_both_legs"],
        "platform": platform,
    })

    streaming = load("serving_bench").run_streaming(argparse.Namespace(
        vocab=96, n_layers=2, d_model=64, n_heads=2,
        max_slots=8, page_size=16,
        stream_counts=os.environ.get("BENCH_STREAM_COUNTS", "16"),
        stream_max_new=16, speculate_k=0,
    ))
    leg = streaming["legs"][-1]
    entries.append({
        "metric": "stream_bytes_per_token",
        "value": leg["push_bin"]["bytes_per_token"],
        "unit": "bytes/token",
        "bytes_per_token_json": leg["push"]["bytes_per_token"],
        "bin_bytes_ratio": leg["bin_bytes_ratio"],
        "streams": leg["streams"],
        "frames_coalesced": streaming["stream_frames_coalesced"],
        "platform": platform,
    })
    return entries


def run_bench() -> dict:
    import jax
    import numpy as np

    from paddle_tpu.core import dtypes, stats
    from paddle_tpu.core.init_ctx import enable_compilation_cache
    from paddle_tpu import models

    # persistent compile cache: repeat bench runs skip the XLA compile; the
    # hit/miss counts land in the JSON line below
    cache_dir = enable_compilation_cache()
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.parallel import DataParallel, make_mesh
    from paddle_tpu.trainer import SGDTrainer

    batch_size = int(os.environ.get("BENCH_BATCH", "256"))
    image_size = int(os.environ.get("BENCH_IMAGE", "224"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "32")))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "1")))
    # steps per compiled dispatch: amortizes host dispatch latency
    scan_k = max(1, int(os.environ.get("BENCH_SCAN", "8")))

    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU; jax's default backend is "
            f"{platform!r} ({devices[0].device_kind}) — a number from it "
            "would not be a device metric"
        )
    peak = peak_tflops(devices[0])

    dtypes.set_policy(dtypes.bf16_policy())
    reset_name_scope()
    img, label, logits, cost = models.resnet50(image_size=image_size)

    mesh = make_mesh({"data": n_dev})
    dp = DataParallel(mesh)

    rs = np.random.RandomState(0)
    batch = {
        "image": rs.randn(batch_size, image_size, image_size, 3).astype(np.float32),
        "label": rs.randint(0, 1000, batch_size),
    }

    from paddle_tpu.core.benchmark import time_multi_steps, time_train_steps

    # Rematerialization lever (PROFILE_r03 "After" table: the residual/BN
    # epilogue bytes): conv_only keeps conv/matmul outputs and recomputes
    # elementwise epilogues in backward — a bytes lever on a bytes-bound
    # model. BENCH_REMAT=none|conv_only|full|auto; auto quick-times both on
    # the real chip and keeps the winner.
    remat_env = os.environ.get("BENCH_REMAT", "auto")
    chosen_remat = None if remat_env in ("none", "") else remat_env
    tune_info = {}
    if remat_env == "auto":
        variants = [None, "conv_only"]
        timings = {}
        for variant in variants:
            t = SGDTrainer(
                cost, SGD(learning_rate=0.1, momentum=0.9), parallel=dp,
                remat=variant, precision="bf16",
            )
            t.init_state(dp.shard_batch(batch))
            stp = t._make_step()
            sec, _ = time_train_steps(
                stp, t.state, dp.shard_batch(batch), steps=3, warmup=1
            )
            timings[str(variant)] = round(sec * 1000, 2)
        chosen_remat = (
            "conv_only"
            if timings["conv_only"] < timings["None"]
            else None
        )
        tune_info = {"remat_tune_ms": timings}
        sys.stderr.write(f"[bench] remat auto-tune: {timings} -> {chosen_remat}\n")

    trainer = SGDTrainer(
        cost, SGD(learning_rate=0.1, momentum=0.9), parallel=dp,
        remat=chosen_remat, precision="bf16",
    )
    trainer.init_state(dp.shard_batch(batch))
    # memory/comms accounting for the data-parallel step (ISSUE 5): per-chip
    # resident opt-state bytes from sharding metadata and the updater's
    # modeled collective bytes/step — benchmarks/shard_update_bench.py sweeps
    # these across replicated/sharded x compression
    opt_state_bytes = stats.per_chip_tree_bytes(trainer.state["opt"])
    collective_bytes = trainer.updater.collective_bytes_per_step()

    # HLO cost buckets (obs pillar 3 / ROADMAP item 2's target list): lower
    # BEFORE the donated timing runs delete the state buffers; the AOT
    # compile for the report happens after timing so it never skews it.
    # BENCH_PROFILE=0 opts out of the one extra XLA compile of the step
    # program.
    profile_on = os.environ.get("BENCH_PROFILE", "1") == "1"
    lowered = None
    if scan_k > 1:
        # K distinct stacked batches per dispatch, scanned inside one
        # compiled program (SGDTrainer.make_multi_step)
        batches = dp.shard_batches(
            {
                "image": rs.randn(
                    scan_k, batch_size, image_size, image_size, 3
                ).astype(np.float32),
                "label": rs.randint(0, 1000, (scan_k, batch_size)),
            }
        )
        multi = trainer.make_multi_step()
        if profile_on:
            lowered = multi.lower(trainer.state, batches)
        dispatches = max(1, steps // scan_k)
        sec_per_step, _ = time_multi_steps(
            multi, trainer.state, batches, scan_k,
            dispatches=dispatches, warmup=warmup,
        )
        steps = dispatches * scan_k
    else:
        step = trainer._make_step()
        batch = dp.shard_batch(batch)
        if profile_on:
            lowered = step.lower(trainer.state, batch)
        sec_per_step, _ = time_train_steps(
            step, trainer.state, batch, steps=steps, warmup=warmup
        )
    dt = sec_per_step * steps

    images_per_sec = batch_size * steps / dt
    images_per_sec_chip = images_per_sec / n_dev

    # ResNet-50 @224 is 4.089 GMACs = 8.18 GFLOPs forward (MACs×2; XLA
    # cost_analysis on the compiled fwd graph reports 7.5e9, same convention
    # modulo elementwise ops — see PROFILE_r03.md). Training (fwd + input-grad
    # + weight-grad) ≈ 3× fwd. Rounds 1-2 used 4.09e9 as if it were FLOPs and
    # UNDERSTATED MFU by 2×.
    flops_per_image = 3 * 8.18e9 * (image_size / 224.0) ** 2
    mfu = images_per_sec_chip * flops_per_image / (peak * 1e12)

    out = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4),
        "mfu": round(mfu, 4),
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "peak_tflops_bf16": peak,
        "precision": "bf16",
        "n_devices": n_dev,
        "batch_size": batch_size,
        "image_size": image_size,
        "ms_per_step": round(1000 * dt / steps, 2),
        "scan_k": scan_k,
        "remat": chosen_remat or "none",
        "opt_state_bytes": opt_state_bytes,
        "collective_bytes_per_step": collective_bytes,
        # BASELINE.json's north-star names v5p hardware; vs_baseline here is
        # MFU/0.50 against THIS chip's peak (device_kind above) — the target
        # is redefined to the available chip, not silently met on v5p
        "baseline_note": "vs_baseline = mfu/0.50 on the available chip, not v5p",
        **tune_info,
    }
    if lowered is not None:
        # top-k FLOP/byte buckets of the timed executable — the
        # profile-driven optimization target list (obs/profile.py; the same
        # report the CLI's --profile pass:N writes)
        try:
            from paddle_tpu.obs.profile import compiled_cost_report

            out["hlo_cost"] = dict(
                compiled_cost_report(lowered.compile(), top_k=3),
                executable="train_step_scan" if scan_k > 1 else "train_step",
            )
        except Exception as exc:  # noqa: BLE001 — report must not kill bench
            sys.stderr.write(f"[bench] hlo cost report failed: {exc!r}\n")
            out["hlo_cost_error"] = repr(exc)[-300:]
    # second runs against a warm cache report misses → 0 (or near it)
    out["compile_cache"] = {
        "dir": cache_dir,
        "hits": stats.RECOMPILES.cache_hits,
        "misses": stats.RECOMPILES.cache_misses,
    }
    # the headline entry lands FIRST in the per-metric stream; a leg that
    # raises ends the run (non-zero exit, no JSON line)
    out["metrics"] = [
        {k: out[k] for k in ("metric", "value", "unit", "mfu", "vs_baseline",
                             "batch_size", "ms_per_step", "platform",
                             "precision")},
    ]
    out["metrics"].append(run_seq2seq(peak))
    out["metrics"].append(run_serving())
    out["metrics"].extend(run_serving_speculative())
    out["metrics"].extend(run_control_plane())
    if n_dev >= 4:
        out["metrics"].append(run_serving_tp())
    else:
        sys.stderr.write(
            f"[bench] serving TP leg left out: the tp4 headline needs >= 4 "
            f"devices, host exposes {n_dev}\n"
        )
    return out


def main() -> None:
    _emit(run_bench())


if __name__ == "__main__":
    main()
