"""Continuous-batching serving benchmark (ISSUE 6 acceptance).

Measures tokens/sec and p50/p99/p999 request latency — plus the
deadline-miss and shed columns (ISSUE 10), zero unless `--deadline_s` arms
per-request deadlines, so overload rounds stay comparable — at 1/4/16/64
concurrent streams against the SAME serving session configuration, where
concurrency=1
is the sequential per-request baseline (one request in flight at a time —
the `run_generation` serving model: nothing overlaps). Same executables,
same platform, same fixed shapes at every concurrency, so the measured
speedup isolates dynamic batching.

The workload is a mixed-length prompt stream spanning two prefill buckets;
after a warmup pass that touches every bucket, the decode-recompile count
must stay at ZERO (the PR-1 RecompileStats assertion — variable-length
sequences of different ages share one compiled decode program through the
paged KV cache).

Acceptance gates (printed in the JSON line):
  * speedup_16 >= 3.0      tokens/sec at 16 streams vs sequential
  * decode_recompiles_after_warmup == 0 over the mixed-length stream
  * mixed-length leg (ISSUE 11): p99 INTER-TOKEN latency with chunked
    prefill <= 0.5x the whole-prompt-prefill baseline at 16 streams when
    long prompts join mid-stream, with identical tokens across the legs

The --tp leg (ISSUE 12) serves identical geometry at TP=1/2/4, one child
process per size with that many FORCED host devices (the shard_update_bench
pattern): tokens must be identical at every TP, per-chip KV-pool bytes
exactly TP× down and param bytes ~TP× down (both from sharding metadata),
zero decode recompiles. Each entry carries its own "platform" tag — CPU
emulates the collectives, so the TP tokens/sec column is a smoke number
there.

The speculative leg (ISSUE 16) runs ONE stream — where batching cannot
help — over high-overlap repeated-motif prompts at --speculate_k 0 vs K:
gates >= 2x single-stream tokens/sec with identical tokens, one compiled
verify signature, zero decode recompiles, and reports the acceptance rate.
The streaming leg pushes the same requests through the router both ways
(poll loop vs push frames) at 1/16/64 streams: gate is push round trips
per delivered token strictly below poll at every count.

The prefix-cache leg (ISSUE 19) serves 4 system prompts x many user turns
that differ only in a short suffix, cache on vs off: gates are prefill
chunk steps AND warm-request TTFT both >= 3x down with the cache on,
tokens bitwise identical on vs off (greedy and seeded sampling, chunked
and whole-prompt prefill), zero page leaks after the index flush, and one
compiled decode signature in every leg (aliasing is a host-side
block-table edit — the executables never see the cache).

The --replicas leg (ISSUE 15) serves identical geometry through the ROUTER
at 1 vs 3 replicas, 64 closed-loop streams: tokens/sec + p99, gate >= 2x
throughput at 3 replicas — armed only on hosts with >= 3 cores (replica
scaling measures hardware parallelism; on a 1-core container the leg still
runs as a correctness + router-overhead drill and records
scaling_gate_meaningful: false).

Usage:
  JAX_PLATFORMS=cpu python benchmarks/serving_bench.py
      [--streams 1,4,16,64] [--requests N] [--max_new N]
      [--tp 1,2,4] [--skip_tp]
      [--vocab V --n_layers L --d_model D --n_heads H]

Output: one JSON line {"metric": "serving_bench", ...} with a per-stream-
count entry (each carrying its own "platform" tag, like shard_update_bench).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_one(args, concurrency: int, prompts):
    """Fresh session per concurrency so KV pool state and stats are clean;
    the persistent compile cache makes the repeat compiles cheap."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    session = make_demo_session(
        vocab=args.vocab, n_layers=args.n_layers, d_model=args.d_model,
        n_heads=args.n_heads, seed=0,
        max_slots=args.max_slots, page_size=args.page_size,
        prefill_buckets=(16, 32), max_new_limit=args.max_new,
    )
    # warmup: touch EVERY prefill bucket + the decode program (one prompt at
    # each bucket length), then snapshot the recompile counter —
    # steady-state serving must add NOTHING to it
    warm_prompts = make_prompts(
        len(session.buckets), lengths=session.buckets, vocab=args.vocab,
        bos_id=1, seed=7,
    )
    warm = run_closed_loop(
        session, warm_prompts, args.max_new, concurrency=len(warm_prompts)
    )
    sigs_after_warmup = session.decode_shape_signatures()
    # the warmup's compile-heavy per-request times never leak into the
    # measured run's load-aware admission: the session resets the EWMA
    # itself at the first clean post-compile step (ISSUE 17)
    res = run_closed_loop(
        session, prompts, args.max_new, concurrency,
        deadline_s=args.deadline_s or None,
    )
    recompiles = session.decode_shape_signatures() - sigs_after_warmup
    tokens = res.pop("results")
    res.update({
        "platform": jax.devices()[0].platform,
        "decode_recompiles_after_warmup": recompiles,
        "decode_shape_signatures": session.decode_shape_signatures(),
        "warmup_tokens": warm["tokens"],
    })
    return res, tokens


def run_mixed_length(args):
    """Chunked-prefill no-stall gate (ISSUE 11): 16 short-prompt streams with
    LONG prompts joining mid-stream, measured as p99 inter-token latency.
    Two legs over identical geometry and workload: whole-prompt prefill (the
    long prompt's full forward runs inside one engine step, stalling every
    running stream's next token) vs chunked prefill (the same prompt commits
    `--prefill_chunk` tokens per step, interleaved with decode). The gate is
    chunked p99 ITL <= 0.5x the whole-prompt baseline — the stall is the
    thing being measured, so this only means anything on the SAME platform
    tag. Tokens must also be identical across the legs (chunked prefill is
    result-transparent)."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import (
        make_mixed_prompts, make_prompts, run_closed_loop,
    )

    long_len = args.mixed_long_len
    buckets = (16, 32, long_len)  # baseline needs a bucket covering the long prompts

    def leg(prefill_chunk):
        # the leg uses its own (bigger) model than the throughput grid: the
        # stall being measured is the long prompt's whole-context forward,
        # which must dominate per-dispatch overhead for the ratio to mean
        # anything — at toy dims the measurement is all dispatch noise
        # page pool sized for the REAL mix (16 short streams + 2 concurrent
        # long prompts), not the worst case of every slot at full context:
        # admission control already queues a long prompt the pool cannot
        # host, and on CPU (no buffer donation) every pool-touching program
        # copies the whole pool, so worst-case sizing would swamp the very
        # stall this leg measures — same pool for BOTH legs, so the ratio
        # isolates chunking
        short_pages = -(-(16 + args.max_new) // args.page_size)
        long_pages = -(-(long_len + args.max_new) // args.page_size)
        num_pages = 20 * short_pages + 2 * args.mixed_burst * long_pages + 1
        # max_slots > stream count: spare slots + a page budget for the burst
        # mean a long prompt admits at the NEXT boundary while all 16 short
        # streams keep decoding — otherwise the burst queues at the FIFO
        # head, admissions behind it stall, and the batch drains before the
        # big prefill even runs (the stall would land on an empty batch and
        # the ITL percentiles would never see it)
        session = make_demo_session(
            vocab=args.vocab, n_layers=args.n_layers,
            d_model=args.mixed_d_model, n_heads=args.mixed_n_heads, seed=0,
            max_slots=20, page_size=args.page_size, num_pages=num_pages,
            prefill_buckets=buckets, max_new_limit=args.max_new,
            max_len=long_len + args.max_new,
            prefill_chunk=prefill_chunk,
        )
        # warmup touches every executable (all buckets + the chunk program +
        # decode) so compile time never pollutes the measured ITL
        warm = make_prompts(
            len(buckets), lengths=buckets, vocab=args.vocab, bos_id=1, seed=7,
        )
        run_closed_loop(session, warm, args.max_new, concurrency=len(warm))
        sigs0 = session.decode_shape_signatures()
        prompts = make_mixed_prompts(
            args.requests, short_lengths=(5, 11, 16), long_len=long_len,
            long_every=12, burst=args.mixed_burst, vocab=args.vocab,
            bos_id=1, seed=1,
        )
        # per-request token budgets STAGGER retirements: with one shared
        # budget every stream retires in the same step, admissions ride the
        # wave boundary, and the whole-prompt stall lands on an empty batch
        # instead of the 16 live streams it is supposed to be measured against
        spread = max(1, args.max_new - 5)
        budgets = [
            args.max_new if len(p) > 16
            else min(args.max_new, 6 + (7 * i) % spread)
            for i, p in enumerate(prompts)
        ]
        # the ITL tail is the measurement: collect BEFORE and hold GC off
        # DURING the run so collector pauses from earlier legs' garbage
        # (the 64-stream grid runs first in a default invocation) don't
        # masquerade as scheduling stalls in either leg's p99
        import gc

        gc.collect()
        gc.disable()
        try:
            res = run_closed_loop(session, prompts, budgets, concurrency=16)
        finally:
            gc.enable()
        tokens = res.pop("results")
        res.update({
            "platform": jax.devices()[0].platform,
            "prefill_chunk": prefill_chunk,
            "long_len": long_len,
            "decode_recompiles_after_warmup":
                session.decode_shape_signatures() - sigs0,
            "prefill_chunks_committed": session.prefill_chunks_committed,
        })
        return res, tokens

    # best-of-N per leg: host noise (GC pauses, CPU contention) lands
    # straight in a single run's p99 tail — the MIN across repeats keeps the
    # deterministic stall component, which is the thing under measurement
    # (alternate the legs so slow host phases hit both)
    whole_runs, chunked_runs = [], []
    for _ in range(args.mixed_repeats):
        whole_runs.append(leg(None))
        chunked_runs.append(leg(args.prefill_chunk))
    whole, whole_tokens = min(
        whole_runs, key=lambda rt: rt[0]["p99_inter_token_ms"]
    )
    chunked, chunked_tokens = min(
        chunked_runs, key=lambda rt: rt[0]["p99_inter_token_ms"]
    )
    ratio = (
        chunked["p99_inter_token_ms"] / whole["p99_inter_token_ms"]
        if whole["p99_inter_token_ms"] > 0 else 0.0
    )
    out = {
        "whole_prompt": whole,
        "chunked": chunked,
        "whole_p99_runs": [r[0]["p99_inter_token_ms"] for r in whole_runs],
        "chunked_p99_runs": [r[0]["p99_inter_token_ms"] for r in chunked_runs],
        "p99_itl_ratio_chunked_vs_whole": round(ratio, 3),
        "chunked_itl_le_half": bool(ratio <= 0.5),
        "chunked_result_transparent": bool(chunked_tokens == whole_tokens),
        "zero_decode_recompiles": bool(
            whole["decode_recompiles_after_warmup"] == 0
            and chunked["decode_recompiles_after_warmup"] == 0
        ),
    }
    print(
        f"[serving_bench] mixed-length: whole p99_itl="
        f"{whole['p99_inter_token_ms']}ms chunked p99_itl="
        f"{chunked['p99_inter_token_ms']}ms ratio={out['p99_itl_ratio_chunked_vs_whole']} "
        f"transparent={out['chunked_result_transparent']}",
        file=sys.stderr,
    )
    return out


def run_speculative(args):
    """The single-stream speculative-decoding leg (ISSUE 16): ONE stream —
    the case continuous batching cannot help, where per-stream latency is
    the whole game — over a high-overlap workload (repeated-motif prompts,
    the extraction/templated-text regime prompt-lookup drafting is built
    for), greedy. Two runs over identical geometry and prompts:
    `--speculate_k 0` (today's one-token decode loop, bit-for-bit the PR-15
    path) vs `--speculate_k K` (draft K from the request's own committed
    tokens, score all K in ONE fixed-shape verify_chunk call). Gates:
      * tokens IDENTICAL across the legs (speculation is result-transparent
        — verification accepts exactly the oracle's tokens)
      * >= 2x single-stream tokens/sec with speculation on
      * verify_shape_signatures == 1 (every round shared one compiled
        [1, K+1] program) and zero decode recompiles in BOTH legs"""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import (
        make_prompts, make_repetitive_prompts, run_closed_loop,
    )

    # the leg runs its own (narrow) vocab: prompt-lookup speculation earns
    # its keep on self-similar text, and a tiny greedy model over a narrow
    # vocab settles into tight repeating continuations — the high-overlap
    # regime the ISSUE names — while a wide-vocab random model wanders for
    # most of a short generation and measures the drafter's worst case
    vocab = args.spec_vocab
    prompts = make_repetitive_prompts(
        args.spec_requests, motif_len=4, repeats=6, vocab=vocab,
        bos_id=1, seed=3,
    )

    def leg(k):
        session = make_demo_session(
            vocab=vocab, n_layers=args.n_layers, d_model=args.d_model,
            n_heads=args.n_heads, seed=0,
            max_slots=4, page_size=args.page_size,
            prefill_buckets=(16, 32), max_new_limit=args.spec_max_new,
            speculate_k=k,
        )
        # warmup touches every prefill bucket + the decode program, and (for
        # the speculative leg) a repetitive prompt long enough to draft so
        # the verify program compiles before the measured window
        warm = make_prompts(
            len(session.buckets), lengths=session.buckets, vocab=vocab,
            bos_id=1, seed=7,
        ) + make_repetitive_prompts(
            1, motif_len=4, repeats=6, vocab=vocab, bos_id=1, seed=11,
        )
        run_closed_loop(session, warm, args.spec_max_new, concurrency=len(warm))
        sigs0 = session.decode_shape_signatures()
        vsigs0 = session.verify_shape_signatures()
        res = run_closed_loop(
            session, prompts, args.spec_max_new, concurrency=1,
        )
        tokens = res.pop("results")
        st = session.stats()
        res.update({
            "platform": jax.devices()[0].platform,
            "speculate_k": k,
            "decode_recompiles_after_warmup":
                session.decode_shape_signatures() - sigs0,
            "verify_recompiles_after_warmup":
                session.verify_shape_signatures() - vsigs0,
            "verify_shape_signatures": st["verify_shape_signatures"],
            "spec_rounds": st["spec_rounds"],
            "spec_acceptance_rate": st["spec_acceptance_rate"],
            "spec_effective_k": st["spec_effective_k"],
        })
        return res, tokens

    # best-of-N per leg, legs alternated: the ratio under measurement is
    # deterministic (steps saved per accepted draft) but each run's wall
    # clock rides host noise — the MAX tokens/sec keeps the structural
    # component, the same discipline as the mixed-length leg's min-p99
    base_runs, spec_runs = [], []
    for _ in range(args.spec_repeats):
        base_runs.append(leg(0))
        spec_runs.append(leg(args.speculate_k))
    base, base_tokens = max(base_runs, key=lambda rt: rt[0]["tokens_per_sec"])
    spec, spec_tokens = max(spec_runs, key=lambda rt: rt[0]["tokens_per_sec"])
    speedup = (
        spec["tokens_per_sec"] / base["tokens_per_sec"]
        if base["tokens_per_sec"] else 0.0
    )
    out = {
        "baseline": base,
        "speculative": spec,
        "single_stream_speedup": round(speedup, 2),
        "spec_tokens_identical": bool(spec_tokens == base_tokens),
        "spec_speedup_ge_2x": bool(speedup >= 2.0),
        "spec_one_verify_signature": bool(
            spec["verify_shape_signatures"] == 1
            and spec["verify_recompiles_after_warmup"] == 0
        ),
        "spec_zero_decode_recompiles": bool(
            base["decode_recompiles_after_warmup"] == 0
            and spec["decode_recompiles_after_warmup"] == 0
        ),
    }
    print(
        f"[serving_bench] speculative k={args.speculate_k}: "
        f"{spec['tokens_per_sec']} tok/s vs {base['tokens_per_sec']} "
        f"(x{out['single_stream_speedup']}) acceptance="
        f"{spec['spec_acceptance_rate']} rounds={spec['spec_rounds']} "
        f"k_eff={spec['spec_effective_k']} "
        f"identical={out['spec_tokens_identical']}",
        file=sys.stderr,
    )
    return out


def run_prefix(args):
    """The shared-prefix KV-cache leg (ISSUE 19): `--prefix_prefixes`
    distinct system prompts, each shared by many user turns that differ only
    in a short random suffix — the many-users-one-assistant regime where a
    million users' prompts are mostly the SAME tokens. Five runs over
    identical geometry and prompts, driven sequentially (one request in
    flight: TTFT is then pure prefill cost, the number the cache attacks):

      A  cache OFF, chunked prefill  (the steps/TTFT baseline)
      B  cache ON,  chunked prefill  (the measured leg)
      C  cache OFF, whole-prompt     (chunk-vs-whole transparency anchor)
      D  cache OFF, chunked, seeded sampling
      E  cache ON,  chunked, seeded sampling

    Gates:
      * prefill chunk steps in B <= 1/Kx of A (warm requests start their one
        chunk at the first uncached token) and warm-request median TTFT down
        by the same >= Kx (K = --prefix_gate_x, default 3)
      * tokens bitwise IDENTICAL: B == A == C (greedy) and E == D (seeded
        sampling) — aliased pages hold exactly the KV the request would have
        computed, under chunked AND whole-prompt prefill
      * zero page leaks: after the run retires every request and the index
        is flushed, every allocatable page is back on the free list
      * decode_shape_signatures == 1 in every leg — the cache is a
        host-side block-table edit, invisible to the compiled programs"""
    import jax
    import numpy as np

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import make_shared_prefix_prompts

    plen = args.prefix_len + args.prefix_suffix
    prompts = make_shared_prefix_prompts(
        args.prefix_requests, n_prefixes=args.prefix_prefixes,
        prefix_len=args.prefix_len, suffix_len=args.prefix_suffix,
        vocab=args.vocab, bos_id=1, seed=5,
    )
    warm_cold = args.prefix_prefixes  # first turn per prefix runs cold

    def leg(prefix_on, temp, chunked=True):
        session = make_demo_session(
            vocab=args.vocab, n_layers=args.n_layers, d_model=args.d_model,
            n_heads=args.n_heads, seed=0,
            max_slots=4, page_size=args.prefix_page_size,
            prefill_buckets=(16, plen), max_new_limit=args.prefix_max_new,
            prefill_chunk=(args.prefix_chunk if chunked else None),
            prefix_cache=prefix_on,
        )
        # warmup compiles the chunk/prefill + decode programs; the flush
        # below guarantees the measured run still starts with a COLD index
        wp = [1] + list(range(3, 3 + plen - 1))
        h = session.submit(wp, args.prefix_max_new)
        session.run_until_idle()
        assert h.done
        if prefix_on:
            session.cache.flush_prefix()
        sigs0 = session.decode_shape_signatures()
        chunks0 = session.stats()["prefill_chunks_committed"]
        ttfts, toks = [], []
        for i, p in enumerate(prompts):
            kw = (
                dict(temperature=temp, top_k=8, seed=1000 + i)
                if temp > 0 else {}
            )
            h = session.submit(p, args.prefix_max_new, **kw)
            session.run_until_idle()
            ttfts.append((h.t_first_token - h.t_submit) * 1e3)
            toks.append(h.tokens)
        st = session.stats()
        leaked = 0
        if prefix_on:
            session.cache.flush_prefix()
        leaked = (session.cache.num_pages - 1) - session.cache.free_pages
        res = {
            "platform": jax.devices()[0].platform,
            "prefix_cache": prefix_on,
            "chunked": chunked,
            "temperature": temp,
            "prefill_chunk_steps": st["prefill_chunks_committed"] - chunks0,
            "ttft_warm_median_ms": round(
                float(np.median(ttfts[warm_cold:])), 3),
            "ttft_cold_median_ms": round(
                float(np.median(ttfts[:warm_cold])), 3),
            "decode_recompiles_after_warmup":
                session.decode_shape_signatures() - sigs0,
            "decode_shape_signatures": session.decode_shape_signatures(),
            "pages_leaked": leaked,
        }
        if prefix_on:
            res.update({
                "prefix_hit_rate": st["prefix_hit_rate"],
                "prefix_pages_shared": st["prefix_pages_shared"],
                "prefix_pages_cow": st["prefix_pages_cow"],
                "prefix_evictions": st["prefix_evictions"],
            })
        return res, toks

    base, base_toks = leg(False, 0.0)            # A
    cached, cached_toks = leg(True, 0.0)         # B
    whole, whole_toks = leg(False, 0.0, chunked=False)  # C
    sbase, sbase_toks = leg(False, 0.7)          # D
    scached, scached_toks = leg(True, 0.7)       # E

    steps_ratio = (
        base["prefill_chunk_steps"] / cached["prefill_chunk_steps"]
        if cached["prefill_chunk_steps"] else 0.0
    )
    ttft_ratio = (
        base["ttft_warm_median_ms"] / cached["ttft_warm_median_ms"]
        if cached["ttft_warm_median_ms"] else 0.0
    )
    out = {
        "baseline": base,
        "cached": cached,
        "whole_prompt": whole,
        "sampled_baseline": sbase,
        "sampled_cached": scached,
        "prefill_steps_ratio": round(steps_ratio, 2),
        "ttft_warm_ratio": round(ttft_ratio, 2),
        "prefix_steps_ge_gate": bool(steps_ratio >= args.prefix_gate_x),
        "prefix_ttft_ge_gate": bool(ttft_ratio >= args.prefix_gate_x),
        "prefix_tokens_identical": bool(
            cached_toks == base_toks and whole_toks == base_toks
        ),
        "prefix_sampled_tokens_identical": bool(scached_toks == sbase_toks),
        "prefix_zero_page_leak": bool(
            cached["pages_leaked"] == 0 and scached["pages_leaked"] == 0
            and base["pages_leaked"] == 0
        ),
        "prefix_one_decode_signature": bool(all(
            r["decode_shape_signatures"] == 1
            and r["decode_recompiles_after_warmup"] == 0
            for r in (base, cached, whole, sbase, scached)
        )),
    }
    print(
        f"[serving_bench] prefix: steps {base['prefill_chunk_steps']} -> "
        f"{cached['prefill_chunk_steps']} (x{out['prefill_steps_ratio']}) "
        f"ttft_warm {base['ttft_warm_median_ms']}ms -> "
        f"{cached['ttft_warm_median_ms']}ms (x{out['ttft_warm_ratio']}) "
        f"hit_rate={cached['prefix_hit_rate']} "
        f"identical={out['prefix_tokens_identical']}/"
        f"{out['prefix_sampled_tokens_identical']} "
        f"leaked={cached['pages_leaked']}",
        file=sys.stderr,
    )
    return out


def run_streaming(args):
    """The push-vs-poll round-trips leg (ISSUE 16): identical requests
    through the ROUTER, delivered two ways — the poll loop every client ran
    before this PR (submit + delta-poll at a fixed interval until done) vs
    push streaming (ONE submit round trip; frames arrive on the same
    connection as the engine emits tokens). The column that matters is
    client round trips per delivered token: polling pays one RPC per
    interval whether or not a token arrived, push pays one RPC per REQUEST.
    Gate: push round-trips-per-token strictly below poll at every stream
    count. Tokens/sec is reported for color but not gated — on a one-box
    CPU run both sides are engine-bound; the wire economics are the
    structural claim.

    ISSUE 20 adds the wire dimension: the push leg runs twice, once over
    the legacy line-JSON wire and once over the framed binary wire
    (compact stream deltas, token payloads packed as int32), and every leg
    reports stream bytes per delivered token off the client's own byte
    counters. Gate: at the LARGEST stream count the binary push spends
    <= half the bytes per token of the JSON push (coalescing under fan-out
    plus the frame encoding carry the 2x)."""
    import threading
    import time

    import jax

    from paddle_tpu.serving.router import RouterServer
    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.server import ServingClient, ServingServer
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    session = make_demo_session(
        vocab=args.vocab, n_layers=args.n_layers, d_model=args.d_model,
        n_heads=args.n_heads, seed=0,
        max_slots=args.max_slots, page_size=args.page_size,
        prefill_buckets=(16, 32), max_new_limit=args.stream_max_new,
        speculate_k=args.speculate_k,
    )
    warm = make_prompts(
        len(session.buckets), lengths=session.buckets, vocab=args.vocab,
        bos_id=1, seed=7,
    )
    run_closed_loop(session, warm, args.stream_max_new, concurrency=len(warm))
    router = RouterServer(lease_s=5.0, poll_interval_s=0.005).start()
    server = ServingServer(session=session, router_endpoints=router.address)
    server.start()
    deadline = time.time() + 30
    while time.time() < deadline and not router.fleet.live():
        time.sleep(0.02)

    def drive(n_streams, mode, wire="json"):
        prompts = make_prompts(
            n_streams, lengths=(5, 11, 16, 23, 32), vocab=args.vocab,
            bos_id=1, seed=100 + n_streams,
        )
        rpcs, tokens_out, errors, nbytes = [0], [0], [0], [0]
        lock = threading.Lock()

        def poll_stream(p):
            c = ServingClient(router.address)
            try:
                rid = c.submit(p, args.stream_max_new)
                calls, cur = 1, 0
                while True:
                    resp = c.poll(rid, from_=cur)
                    calls += 1
                    if "err" in resp:
                        raise RuntimeError(resp["err"])
                    if resp.get("done"):
                        toks = resp["tokens"]
                        break
                    cur = int(resp.get("tokens_so_far", cur))
                    time.sleep(0.02)
                with lock:
                    rpcs[0] += calls
                    tokens_out[0] += len(toks)
            except Exception:
                with lock:
                    errors[0] += 1
            finally:
                c.close()

        def push_stream(p):
            c = ServingClient(router.address, wire=wire)
            try:
                n = 0
                for frame in c.stream(p, args.stream_max_new):
                    n = int(frame.get("tokens_so_far", n))
                # one round trip per (re)attach: the submit ack; every frame
                # after it is pushed on the same connection
                with lock:
                    rpcs[0] += 1 + c.stream_reattaches
                    tokens_out[0] += n
                    nbytes[0] += c.stream_bytes_in
            except Exception:
                with lock:
                    errors[0] += 1
            finally:
                c.close()

        fn = poll_stream if mode == "poll" else push_stream
        threads = [
            threading.Thread(target=fn, args=(p,), daemon=True)
            for p in prompts
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall = time.monotonic() - t0
        return {
            "mode": mode,
            "wire": wire,
            "streams": n_streams,
            "tokens": tokens_out[0],
            "errors": errors[0],
            "round_trips": rpcs[0],
            "round_trips_per_token": round(
                rpcs[0] / tokens_out[0], 3
            ) if tokens_out[0] else 0.0,
            "stream_bytes": nbytes[0],
            "bytes_per_token": round(
                nbytes[0] / tokens_out[0], 1
            ) if tokens_out[0] else 0.0,
            "tokens_per_sec": round(tokens_out[0] / wall, 1) if wall else 0.0,
        }

    legs = []
    try:
        for n in [int(x) for x in args.stream_counts.split(",") if x.strip()]:
            poll = drive(n, "poll")
            push = drive(n, "push", wire="json")
            push_bin = drive(n, "push", wire="frames")
            legs.append({
                "streams": n,
                "poll": poll,
                "push": push,
                "push_bin": push_bin,
                "push_fewer_round_trips_per_token": bool(
                    push["errors"] == 0 and poll["errors"] == 0
                    and push["round_trips_per_token"]
                    < poll["round_trips_per_token"]
                ),
                "bin_bytes_ratio": round(
                    push["bytes_per_token"] / push_bin["bytes_per_token"], 2
                ) if push_bin["bytes_per_token"] else 0.0,
            })
            print(
                f"[serving_bench] streaming streams={n}: push "
                f"{push['round_trips_per_token']} rt/token vs poll "
                f"{poll['round_trips_per_token']}; bytes/token json "
                f"{push['bytes_per_token']} vs binary "
                f"{push_bin['bytes_per_token']} "
                f"(frames pushed so far: {router.stream_frames}, "
                f"coalesced: {router.stream_coalesced})",
                file=sys.stderr,
            )
    finally:
        server.stop()
        router.stop()
    return {
        "platform": jax.devices()[0].platform,
        "legs": legs,
        "push_round_trips_below_poll_all": bool(legs) and all(
            l["push_fewer_round_trips_per_token"] for l in legs
        ),
        # ISSUE 20 gate: at the largest fan-out the binary push wire moves
        # <= half the bytes per delivered token of the JSON push wire
        "binary_stream_bytes_2x_at_max_fanout": bool(legs) and (
            legs[-1]["bin_bytes_ratio"] >= 2.0
        ),
        "stream_frames_pushed": router.stream_frames,
        "stream_bytes_pushed": router.stream_bytes,
        "stream_frames_coalesced": router.stream_coalesced,
    }


def run_tp_child(args):
    """One tensor-parallel leg in THIS process (forced host device count is
    already set by the parent re-exec): identical geometry at every TP, so
    the tokens/sec + p99 ITL deltas isolate the collectives, and the
    per-chip param/pool bytes come from sharding metadata."""
    import jax

    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    tp = args._child_tp
    session = make_demo_session(
        vocab=args.vocab, n_layers=args.n_layers, d_model=args.d_model,
        n_heads=args.tp_n_heads, seed=0,
        max_slots=args.max_slots, page_size=args.page_size,
        prefill_buckets=(16, 32), max_new_limit=args.max_new,
        tp=(tp if tp > 1 else 0),
    )
    prompts = make_prompts(
        args.requests, lengths=(5, 11, 16, 23, 32), vocab=args.vocab,
        bos_id=1, seed=0,
    )
    warm = make_prompts(
        len(session.buckets), lengths=session.buckets, vocab=args.vocab,
        bos_id=1, seed=7,
    )
    run_closed_loop(session, warm, args.max_new, concurrency=len(warm))
    sigs0 = session.decode_shape_signatures()
    res = run_closed_loop(session, prompts, args.max_new, concurrency=16)
    tokens = res.pop("results")
    st = session.stats()
    res.update({
        "tp": tp,
        "platform": jax.devices()[0].platform,
        "devices": jax.device_count(),
        "decode_recompiles_after_warmup":
            session.decode_shape_signatures() - sigs0,
        "param_bytes_per_chip": st["param_bytes_per_chip"],
        "pool_bytes_per_chip": st["pool_bytes_per_chip"],
        "results": tokens,
    })
    print("TP_BENCH_JSON " + json.dumps(res))


def run_tp(args):
    """The --tp leg (ISSUE 12): TP=1/2/4 over identical geometry, each in a
    child process with the XLA host device count FORCED to the TP size (the
    shard_update_bench pattern — the device count is fixed at backend
    init). Gates: tokens identical at
    every TP (tensor parallelism is result-invisible), zero decode
    recompiles, and per-chip pool bytes exactly TP× down."""
    legs = []
    for n in [int(x) for x in args.tp.split(",") if x.strip()]:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "").replace(
                "--xla_force_host_platform_device_count=8", ""
            )
            + f" --xla_force_host_platform_device_count={max(n, 1)}"
        ).strip()
        cmd = [
            sys.executable, os.path.abspath(__file__),
            f"--_child_tp={n}", f"--requests={args.requests}",
            f"--max_new={args.max_new}", f"--max_slots={args.max_slots}",
            f"--page_size={args.page_size}", f"--vocab={args.vocab}",
            f"--n_layers={args.n_layers}", f"--d_model={args.d_model}",
            f"--tp_n_heads={args.tp_n_heads}",
        ]
        try:
            out = subprocess.run(
                cmd, capture_output=True, text=True, timeout=1200, env=env,
            )
        except (subprocess.TimeoutExpired, OSError) as exc:
            # a wedged/unspawnable child is an ERROR LEG, not a bench abort:
            # the streams grid + mixed-length results already computed must
            # still reach the JSON line
            legs.append({"tp": n, "error": repr(exc)[-500:]})
            continue
        line = next(
            (l for l in out.stdout.splitlines()
             if l.startswith("TP_BENCH_JSON ")), None,
        )
        if line is None:
            legs.append({"tp": n, "error": (out.stderr or out.stdout)[-500:]})
        else:
            legs.append(json.loads(line[len("TP_BENCH_JSON "):]))
    ok_legs = [l for l in legs if "error" not in l]
    token_sets = {l["tp"]: l.pop("results") for l in ok_legs}
    base = next((l for l in ok_legs if l["tp"] <= 1), None)
    identical = (
        len(token_sets) == len(legs) and len(set(
            json.dumps(t) for t in token_sets.values()
        )) == 1
    )
    gates = {
        "tp_tokens_identical": bool(identical),
        "tp_zero_decode_recompiles": bool(ok_legs) and all(
            l["decode_recompiles_after_warmup"] == 0 for l in ok_legs
        ),
    }
    for leg in ok_legs:
        if base is None or leg["tp"] <= 1:
            continue
        n = leg["tp"]
        gates[f"tp{n}_pool_bytes_ratio"] = round(
            base["pool_bytes_per_chip"] / max(leg["pool_bytes_per_chip"], 1), 2
        )
        gates[f"tp{n}_pool_bytes_exact"] = bool(
            leg["pool_bytes_per_chip"] * n == base["pool_bytes_per_chip"]
        )
        gates[f"tp{n}_param_bytes_ratio"] = round(
            base["param_bytes_per_chip"] / max(leg["param_bytes_per_chip"], 1),
            2,
        )
        gates[f"tp{n}_param_bytes_reduced_enough"] = bool(
            base["param_bytes_per_chip"]
            >= 0.6 * n * leg["param_bytes_per_chip"]
        )
        print(
            f"[serving_bench] tp={n}: {leg['tokens_per_sec']} tok/s "
            f"p99_itl={leg['p99_inter_token_ms']}ms "
            f"pool_bytes/chip={leg['pool_bytes_per_chip']} "
            f"(ratio {gates[f'tp{n}_pool_bytes_ratio']}x) "
            f"identical={identical}",
            file=sys.stderr,
        )
    return {"legs": legs, "gates": gates}


def run_replicas(args):
    """The --replicas leg (ISSUE 15): identical geometry served by 1 vs N
    replicas behind the router at `--replica_streams` concurrent streams.
    Each stream is a thread keeping one request in flight (submit → result →
    next, pulling from a shared work list), so N replicas get to fill N
    engines' slots concurrently; the gate is >= 2x tokens/sec at 3 replicas
    (engines run jit'd programs that release the GIL, so in-process replicas
    genuinely overlap — on a host with the cores to back them). The gate is
    only ARMED with >= 3 host cores: replica scaling measures hardware
    parallelism, and on a 1-core container 3 engines time-slice one core, so
    aggregate tokens/sec physically cannot scale — the leg still runs there
    as a correctness + router-overhead drill (all requests complete, zero
    failovers, the ratio reported) with `scaling_gate_meaningful: false`
    recorded, the same machine-readable-caveat discipline as the bf16
    speedup gate on the CPU fallback. Sessions are warmed DIRECTLY before
    joining the fleet so compile time never pollutes the measured window;
    every entry carries its own platform tag."""
    import threading
    import time

    import numpy as np

    import jax

    from paddle_tpu.serving.router import RouterServer
    from paddle_tpu.serving.session import make_demo_session
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.workload import make_prompts, run_closed_loop

    def leg(n_replicas):
        sessions = []
        for _ in range(n_replicas):
            s = make_demo_session(
                vocab=args.vocab, n_layers=args.n_layers,
                d_model=args.replicas_d_model, n_heads=4, seed=0,
                max_slots=args.max_slots, page_size=args.page_size,
                prefill_buckets=(16, 32), max_new_limit=args.max_new,
            )
            warm = make_prompts(
                len(s.buckets), lengths=s.buckets, vocab=args.vocab,
                bos_id=1, seed=7,
            )
            run_closed_loop(s, warm, args.max_new, concurrency=len(warm))
            sessions.append(s)
        router = RouterServer(lease_s=5.0, poll_interval_s=0.005).start()
        servers = [
            ServingServer(session=s, router_endpoints=router.address).start()
            for s in sessions
        ]
        deadline = time.time() + 30
        while (time.time() < deadline
               and len(router.fleet.live()) < n_replicas):
            time.sleep(0.02)
        prompts = make_prompts(
            args.replicas_requests, lengths=(5, 11, 16, 23, 32),
            vocab=args.vocab, bos_id=1, seed=0,
        )
        work = list(enumerate(prompts))
        work_lock = threading.Lock()
        lat_ms, tokens_out, errors = [], [0], [0]

        def stream():
            while True:
                with work_lock:
                    if not work:
                        return
                    _idx, p = work.pop(0)
                t1 = time.monotonic()
                try:
                    h = router.router.submit(p, args.max_new)
                    toks = h.result(timeout=180.0)
                except Exception:
                    with work_lock:
                        errors[0] += 1
                    continue
                with work_lock:
                    lat_ms.append((time.monotonic() - t1) * 1e3)
                    tokens_out[0] += len(toks)

        threads = [
            threading.Thread(target=stream, daemon=True)
            for _ in range(args.replica_streams)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.monotonic() - t0
        st = router.router.stats()
        for srv in servers:
            srv.stop()
        router.stop()
        lat = np.asarray(lat_ms) if lat_ms else np.asarray([0.0])
        return {
            "replicas": n_replicas,
            "streams": args.replica_streams,
            "requests": args.replicas_requests,
            "completed": len(lat_ms),
            "errors": errors[0],
            "tokens": tokens_out[0],
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(tokens_out[0] / wall, 1) if wall else 0.0,
            "p50_latency_ms": round(float(np.percentile(lat, 50)), 2),
            "p99_latency_ms": round(float(np.percentile(lat, 99)), 2),
            "router_failovers": st["failovers"],
            "platform": jax.devices()[0].platform,
        }

    legs = [
        leg(int(x)) for x in args.replicas.split(",") if x.strip()
    ]
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    by_n = {l["replicas"]: l for l in legs}
    base = by_n.get(1)
    gates = {"host_cores": cores, "scaling_gate_meaningful": cores >= 3}
    for l in legs:
        if base is None or l["replicas"] <= 1:
            continue
        ratio = (
            l["tokens_per_sec"] / base["tokens_per_sec"]
            if base["tokens_per_sec"] else 0.0
        )
        gates[f"replicas{l['replicas']}_speedup_vs_1"] = round(ratio, 2)
        if l["replicas"] == 3:
            # the >= 2x scaling gate needs >= 3 cores to mean anything; on a
            # smaller host record the ratio and leave the gate un-armed
            gates["replicas3_speedup_ge_2x"] = (
                bool(ratio >= 2.0) if cores >= 3 else None
            )
        print(
            f"[serving_bench] replicas={l['replicas']}: "
            f"{l['tokens_per_sec']} tok/s p99={l['p99_latency_ms']}ms "
            f"(x{ratio:.2f} vs 1 replica; {cores} host core(s))",
            file=sys.stderr,
        )
    gates["replicas_all_completed"] = all(
        l["completed"] == l["requests"] and l["errors"] == 0 for l in legs
    )
    gates["replicas_zero_failovers"] = all(
        l["router_failovers"] == 0 for l in legs
    )
    return {"legs": legs, "gates": gates}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", default="1,4,16,64")
    ap.add_argument("--requests", type=int, default=48,
                    help="total requests per concurrency level")
    ap.add_argument("--max_new", type=int, default=24)
    ap.add_argument("--deadline_s", type=float, default=0.0,
                    help="arm a per-request total-latency deadline (0 = "
                         "none); the p999 / deadline-miss columns report "
                         "either way so rounds stay comparable")
    ap.add_argument("--max_slots", type=int, default=16)
    ap.add_argument("--page_size", type=int, default=16)
    ap.add_argument("--prefill_chunk", type=int, default=16,
                    help="chunk size for the mixed-length leg's chunked side")
    ap.add_argument("--mixed_long_len", type=int, default=640,
                    help="long-prompt length joining mid-stream in the "
                         "mixed-length leg")
    ap.add_argument("--mixed_d_model", type=int, default=256)
    ap.add_argument("--mixed_burst", type=int, default=3,
                    help="long prompts arriving together in each burst")
    ap.add_argument("--mixed_repeats", type=int, default=3,
                    help="repeats per mixed-length leg; min-p99 is reported "
                         "(filters host-noise spikes out of the tail)")
    ap.add_argument("--mixed_n_heads", type=int, default=4)
    ap.add_argument("--skip_mixed", action="store_true",
                    help="skip the mixed-length chunked-prefill leg")
    ap.add_argument("--tp", default="1,2,4",
                    help="tensor-parallel leg (ISSUE 12): comma list of TP "
                         "sizes, each run in a child with that many forced "
                         "host devices over identical geometry; empty "
                         "string skips the leg")
    ap.add_argument("--tp_n_heads", type=int, default=4,
                    help="head count for the --tp leg (must divide by every "
                         "TP size; the main grid keeps --n_heads)")
    ap.add_argument("--skip_tp", action="store_true",
                    help="skip the tensor-parallel leg")
    ap.add_argument("--replicas", default="1,3",
                    help="router-fleet leg (ISSUE 15): comma list of replica "
                         "counts served through the router at "
                         "--replica_streams streams; empty string skips")
    ap.add_argument("--replica_streams", type=int, default=64,
                    help="concurrent closed-loop streams through the router")
    ap.add_argument("--replicas_requests", type=int, default=192,
                    help="total requests per replica-count leg")
    ap.add_argument("--replicas_d_model", type=int, default=128,
                    help="model width for the --replicas leg: the engines "
                         "must dominate dispatch overhead for the scaling "
                         "gate to measure replica parallelism")
    ap.add_argument("--skip_replicas", action="store_true",
                    help="skip the router-fleet replica-scaling leg")
    ap.add_argument("--speculate_k", type=int, default=8,
                    help="draft length for the speculative single-stream leg "
                         "and the streaming leg's engine (ISSUE 16)")
    ap.add_argument("--spec_requests", type=int, default=8,
                    help="requests in the single-stream speculative leg")
    ap.add_argument("--spec_max_new", type=int, default=64,
                    help="tokens per request in the speculative leg (long "
                         "enough to amortize prefill out of the ratio, and "
                         "for the greedy continuation to settle into the "
                         "self-similar tail the drafter feeds on)")
    ap.add_argument("--spec_vocab", type=int, default=32,
                    help="vocab for the speculative leg's own model (narrow "
                         "= high-overlap greedy continuations)")
    ap.add_argument("--spec_repeats", type=int, default=2,
                    help="repeats per speculative leg; best tokens/sec is "
                         "compared (filters host noise out of the ratio)")
    ap.add_argument("--skip_spec", action="store_true",
                    help="skip the single-stream speculative-decoding leg")
    ap.add_argument("--prefix_requests", type=int, default=24,
                    help="user turns in the shared-prefix leg (ISSUE 19)")
    ap.add_argument("--prefix_prefixes", type=int, default=4,
                    help="distinct system prompts the turns cycle over")
    ap.add_argument("--prefix_len", type=int, default=56,
                    help="shared system-prompt length in tokens")
    ap.add_argument("--prefix_suffix", type=int, default=8,
                    help="per-user unique suffix length in tokens")
    ap.add_argument("--prefix_chunk", type=int, default=8,
                    help="prefill chunk for the prefix leg (a warm request "
                         "pays ONE chunk: its own suffix)")
    ap.add_argument("--prefix_page_size", type=int, default=8,
                    help="KV page size for the prefix leg (the aliasing "
                         "granularity)")
    ap.add_argument("--prefix_max_new", type=int, default=8)
    ap.add_argument("--prefix_gate_x", type=float, default=3.0,
                    help="required prefill-steps AND warm-TTFT reduction "
                         "factor, cache on vs off")
    ap.add_argument("--skip_prefix", action="store_true",
                    help="skip the shared-prefix KV-cache leg")
    ap.add_argument("--stream_counts", default="1,16,64",
                    help="stream counts for the push-vs-poll round-trips "
                         "leg; empty string skips")
    ap.add_argument("--stream_max_new", type=int, default=24)
    ap.add_argument("--skip_streaming", action="store_true",
                    help="skip the push-vs-poll streaming leg")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--n_layers", type=int, default=2)
    ap.add_argument("--d_model", type=int, default=64)
    ap.add_argument("--n_heads", type=int, default=2)
    ap.add_argument("--_child_tp", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._child_tp:
        run_tp_child(args)
        return

    from paddle_tpu.serving.model import LMConfig
    from paddle_tpu.serving.workload import make_prompts

    cfg = LMConfig(vocab=args.vocab)
    # mixed lengths across BOTH buckets (16 and 32): the zero-recompile gate
    # is only meaningful on a shape-diverse stream
    prompts = make_prompts(
        args.requests, lengths=(5, 11, 16, 23, 32), vocab=args.vocab,
        bos_id=cfg.bos_id, seed=0,
    )

    results = []
    token_sets = {}
    for n in [int(x) for x in args.streams.split(",") if x.strip()]:
        res, tokens = run_one(args, n, prompts)
        results.append(res)
        token_sets[n] = tokens
        print(
            f"[serving_bench] streams={n}: {res['tokens_per_sec']} tok/s "
            f"p50={res['p50_latency_ms']}ms p99={res['p99_latency_ms']}ms "
            f"p999={res['p999_latency_ms']}ms "
            f"deadline_misses={res['deadline_misses']} "
            f"recompiles={res['decode_recompiles_after_warmup']}",
            file=sys.stderr,
        )

    by_n = {r["concurrency"]: r for r in results}
    base = by_n.get(1)
    for r in results:
        if base is not None and base["tokens_per_sec"] > 0:
            r["speedup_vs_sequential"] = round(
                r["tokens_per_sec"] / base["tokens_per_sec"], 2
            )
    # continuous batching must be RESULT-transparent, not just fast: every
    # concurrency level produced identical tokens for every request
    consistent = all(t == token_sets[min(token_sets)] for t in token_sets.values())
    speedup_16 = by_n.get(16, {}).get("speedup_vs_sequential", 0.0)
    mixed = None if args.skip_mixed else run_mixed_length(args)
    spec = (
        None if (args.skip_spec or args.speculate_k <= 0)
        else run_speculative(args)
    )
    prefix = None if args.skip_prefix else run_prefix(args)
    streaming = (
        None if (args.skip_streaming or not args.stream_counts.strip())
        else run_streaming(args)
    )
    tp = None if (args.skip_tp or not args.tp.strip()) else run_tp(args)
    replicas = (
        None if (args.skip_replicas or not args.replicas.strip())
        else run_replicas(args)
    )
    gates = {
        "speedup_16_vs_sequential": speedup_16,
        "speedup_16_ge_3x": bool(speedup_16 >= 3.0),
        "zero_decode_recompiles": all(
            r["decode_recompiles_after_warmup"] == 0 for r in results
        ),
        "batching_bitwise_transparent": bool(consistent),
    }
    ok = gates["speedup_16_ge_3x"] and gates["zero_decode_recompiles"] and consistent
    if mixed is not None:
        gates["mixed_chunked_itl_le_half_whole"] = mixed["chunked_itl_le_half"]
        gates["mixed_chunked_result_transparent"] = (
            mixed["chunked_result_transparent"]
        )
        gates["mixed_zero_decode_recompiles"] = mixed["zero_decode_recompiles"]
        ok = (ok and mixed["chunked_itl_le_half"]
              and mixed["chunked_result_transparent"]
              and mixed["zero_decode_recompiles"])
    if spec is not None:
        gates["spec_single_stream_speedup"] = spec["single_stream_speedup"]
        gates["spec_speedup_ge_2x"] = spec["spec_speedup_ge_2x"]
        gates["spec_tokens_identical"] = spec["spec_tokens_identical"]
        gates["spec_one_verify_signature"] = spec["spec_one_verify_signature"]
        gates["spec_acceptance_rate"] = (
            spec["speculative"]["spec_acceptance_rate"]
        )
        ok = (ok and spec["spec_speedup_ge_2x"]
              and spec["spec_tokens_identical"]
              and spec["spec_one_verify_signature"]
              and spec["spec_zero_decode_recompiles"])
    if prefix is not None:
        gates["prefix_prefill_steps_ratio"] = prefix["prefill_steps_ratio"]
        gates["prefix_ttft_warm_ratio"] = prefix["ttft_warm_ratio"]
        gates["prefix_steps_ge_gate"] = prefix["prefix_steps_ge_gate"]
        gates["prefix_ttft_ge_gate"] = prefix["prefix_ttft_ge_gate"]
        gates["prefix_tokens_identical"] = prefix["prefix_tokens_identical"]
        gates["prefix_sampled_tokens_identical"] = (
            prefix["prefix_sampled_tokens_identical"]
        )
        gates["prefix_zero_page_leak"] = prefix["prefix_zero_page_leak"]
        gates["prefix_one_decode_signature"] = (
            prefix["prefix_one_decode_signature"]
        )
        gates["prefix_hit_rate"] = prefix["cached"]["prefix_hit_rate"]
        ok = (ok and prefix["prefix_steps_ge_gate"]
              and prefix["prefix_ttft_ge_gate"]
              and prefix["prefix_tokens_identical"]
              and prefix["prefix_sampled_tokens_identical"]
              and prefix["prefix_zero_page_leak"]
              and prefix["prefix_one_decode_signature"])
    if streaming is not None:
        gates["push_round_trips_below_poll_all"] = (
            streaming["push_round_trips_below_poll_all"]
        )
        ok = ok and streaming["push_round_trips_below_poll_all"]
    if tp is not None:
        gates.update(tp["gates"])
        ok = (ok and tp["gates"]["tp_tokens_identical"]
              and tp["gates"]["tp_zero_decode_recompiles"]
              and all(v for k, v in tp["gates"].items()
                      if k.endswith(("_pool_bytes_exact",
                                     "_param_bytes_reduced_enough"))))
    if replicas is not None:
        gates.update(replicas["gates"])
        # the scaling gate only votes when armed (>= 3 host cores); None =
        # structurally unmeasurable on this host, recorded not failed
        ok = (ok and replicas["gates"].get("replicas_all_completed", True)
              and replicas["gates"].get("replicas_zero_failovers", True)
              and replicas["gates"].get("replicas3_speedup_ge_2x") is not False)
    print(json.dumps({
        "metric": "serving_bench",
        "value": speedup_16,
        "unit": "x tokens/sec vs sequential @16 streams",
        "all_gates_pass": bool(ok),
        "gates": gates,
        "results": results,
        "mixed_length": mixed,
        "speculative": spec,
        "prefix_cache": prefix,
        "streaming": streaming,
        "tensor_parallel": tp,
        "router_replicas": replicas,
    }))


if __name__ == "__main__":
    main()
