"""Closed-loop serving workloads: N concurrent streams vs sequential.

Driven by chip_smoke.py's served round, tests/test_tp_serving.py and the
drills of benchmarks/chaos_bench.py (the benchmark's own traffic is under
perfbench/traffic/).

A "stream" models one user connection: it keeps exactly one request in
flight, submitting its next request the moment the previous one completes —
so `concurrency=N` holds N requests live and continuous batching gets to
fill up to N slots per decode step. `concurrency=1` IS the sequential
per-request baseline (same executables, same platform, same shapes): the
measured speedup isolates dynamic batching, not kernel differences."""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np


def make_prompts(
    n: int,
    lengths: Sequence[int],
    vocab: int,
    bos_id: int,
    seed: int = 0,
) -> List[List[int]]:
    """Deterministic mixed-length prompts (BOS + random ids; never EOS so
    lengths are workload-controlled, not sampling-controlled)."""
    rs = np.random.RandomState(seed)
    out = []
    for i in range(n):
        ln = int(lengths[i % len(lengths)])
        body = rs.randint(3, vocab, size=ln - 1)
        out.append([bos_id] + [int(t) for t in body])
    return out


def make_mixed_prompts(
    n: int,
    short_lengths: Sequence[int],
    long_len: int,
    long_every: int,
    vocab: int,
    bos_id: int,
    seed: int = 0,
    burst: int = 3,
) -> List[List[int]]:
    """The chunked-prefill workload (ISSUE 11): a steady short-prompt stream
    with a BURST of `burst` long prompts joining every `long_every` requests
    mid-stream. Bursts are the adversarial arrival pattern for whole-prompt
    prefill: every long prompt admitted at one step boundary runs its full
    forward serially inside that single engine step, so the running streams'
    inter-token gap is burst_size × prefill — exactly the stall chunked
    prefill bounds to one chunk per step."""
    base = make_prompts(n, lengths=short_lengths, vocab=vocab, bos_id=bos_id,
                        seed=seed)
    rs = np.random.RandomState(seed + 1)
    for i in range(long_every // 2, n, long_every):
        for j in range(i, min(i + burst, n)):
            body = rs.randint(3, vocab, size=long_len - 1)
            base[j] = [bos_id] + [int(t) for t in body]
    return base


def run_closed_loop(
    session,
    prompts: List[List[int]],
    max_new_tokens,  # int, or a per-prompt list (staggers retirements)
    concurrency: int,
    tenant: str = "default",
    deadline_s: Optional[float] = None,
    ttft_deadline_s: Optional[float] = None,
) -> Dict:
    """Drive `session` single-threaded: keep up to `concurrency` requests in
    flight, stepping the engine until all prompts complete. Returns
    tokens/sec plus p50/p99/p999 request latency, the INTER-TOKEN latency
    percentiles (gap between consecutive tokens of one stream, observed at
    engine-step boundaries — the number a whole-prompt prefill stall shows
    up in and chunked prefill must keep flat, ISSUE 11), and (when deadlines
    are armed) the deadline-miss and shed columns — present either way, so
    bench rounds stay comparable. Throughput and the percentiles count only
    requests that COMPLETED: a deadline-cancelled request's partial tokens
    and truncated latency would otherwise flatter the overloaded run
    (higher tok/s, lower p99) exactly when it is failing."""
    from paddle_tpu.serving.quota import QuotaExceeded

    budgets = (
        list(max_new_tokens) if isinstance(max_new_tokens, (list, tuple))
        else [max_new_tokens] * len(prompts)
    )
    pending = list(enumerate(prompts))
    in_flight = {}  # request_id -> (index, handle)
    latencies_ms: List[float] = []
    itl_ms: List[float] = []  # inter-token gaps across ALL streams
    token_seen = {}  # request_id -> (token_count, t_last_token)
    tokens_out = 0
    shed = 0
    deadline_missed = 0
    results: List[Optional[List[int]]] = [None] * len(prompts)

    t0 = time.monotonic()
    while pending or in_flight:
        while pending and len(in_flight) < concurrency:
            idx, prompt = pending.pop(0)
            try:
                h = session.submit(
                    prompt, budgets[idx], tenant=tenant,
                    deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s,
                )
            except QuotaExceeded:
                shed += 1
                continue
            in_flight[h.request_id] = (idx, h)
            token_seen[h.request_id] = (0, None)
        session.step()
        now = time.monotonic()
        # inter-token latency: a stream's gap between consecutive tokens,
        # measured from this driver's step boundary (first token = TTFT,
        # excluded — ITL isolates the steady-stream stall a co-scheduled
        # prefill causes). A step may deliver SEVERAL tokens to one stream
        # (a speculative verify round, ISSUE 16): the gap amortizes over
        # them and each delivered token contributes ONE sample, so the
        # percentiles stay per-token — a multi-token step must pull p50
        # down in proportion to the tokens it delivered, not count once
        # alongside the single-token steps
        for rid, (_, h) in in_flight.items():
            n_prev, t_prev = token_seen[rid]
            n_now = len(h.tokens)
            if n_now > n_prev:
                if t_prev is not None:
                    gap = (now - t_prev) * 1e3 / (n_now - n_prev)
                    itl_ms.extend([gap] * (n_now - n_prev))
                token_seen[rid] = (n_now, now)
        done = [rid for rid, (_, h) in in_flight.items() if h.done]
        for rid in done:
            idx, h = in_flight.pop(rid)
            token_seen.pop(rid, None)
            if h.status == h.DONE:
                results[idx] = h.tokens
                tokens_out += len(h.tokens)
                latencies_ms.append((h.t_done - h.t_submit) * 1e3)
            elif h.finish_reason == "deadline":
                deadline_missed += 1
    dt = time.monotonic() - t0

    lat = np.asarray(latencies_ms) if latencies_ms else np.asarray([0.0])
    itl = np.asarray(itl_ms) if itl_ms else np.asarray([0.0])
    accepted = len(latencies_ms) + deadline_missed
    return {
        "concurrency": concurrency,
        "requests": len(prompts),
        "tokens": tokens_out,
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(tokens_out / dt, 1) if dt > 0 else 0.0,
        "p50_latency_ms": round(float(np.percentile(lat, 50)), 2),
        "p99_latency_ms": round(float(np.percentile(lat, 99)), 2),
        "p999_latency_ms": round(float(np.percentile(lat, 99.9)), 2),
        "p50_inter_token_ms": round(float(np.percentile(itl, 50)), 3),
        "p99_inter_token_ms": round(float(np.percentile(itl, 99)), 3),
        "shed": shed,
        "deadline_misses": deadline_missed,
        "deadline_miss_ratio": round(deadline_missed / accepted, 4)
        if accepted else 0.0,
        "results": results,
    }


def expand_schedule(
    n: int,
    schedule: Sequence,  # [(duration_s, rate_rps), ...]
) -> List:
    """Flatten a time-varying load schedule into absolute arrival offsets.

    Each `(duration_s, rate_rps)` phase contributes evenly spaced arrivals
    for its duration (rate 0 = an idle phase: time passes, nothing arrives).
    Returns `[(offset_s, phase_idx), ...]`, at most `n` entries — shared by
    `run_open_loop` and the chaos bench's autoscale drill (which replays the
    same offsets against a ROUTER instead of an engine), so "the burst" is
    the identical arrival pattern in both."""
    arrivals = []
    t = 0.0
    for p, (dur, rate) in enumerate(schedule):
        dur = float(dur)
        rate = float(rate)
        if rate > 0:
            interval = 1.0 / rate
            k = 0
            while k * interval < dur and len(arrivals) < n:
                arrivals.append((t + k * interval, p))
                k += 1
        t += dur
    return arrivals


def run_open_loop(
    session,
    prompts: List[List[int]],
    max_new_tokens: int,
    rate_rps: Optional[float] = None,
    tenants: Sequence[str] = ("default",),
    deadline_s: Optional[float] = None,
    ttft_deadline_s: Optional[float] = None,
    schedule: Optional[Sequence] = None,
) -> Dict:
    """Open-loop (offered-load) driver — the overload model: arrivals land
    on a fixed offered schedule REGARDLESS of completions, so offered load
    above capacity builds a queue instead of throttling itself (the closed
    loop can never overload a server; this is what exercises shedding). The
    engine is driven inline on this thread, one step per iteration, arrivals
    replayed from the precomputed schedule, so a run is reproducible modulo
    host timing.

    Offered load is either a constant `rate_rps`, or a time-varying
    `schedule` of `(duration_s, rate_rps)` phases (ISSUE 17: the autoscale
    gate's idle → burst → idle shape). With a schedule, the report gains a
    `phases` list — per-phase offered/shed/goodput — because a burst phase's
    collapse would otherwise be averaged away by its idle neighbours.

    Goodput = requests that completed WITHIN their deadline per second of
    wall clock — the number the chaos bench's 2× overload gate compares
    against the at-capacity run."""
    from paddle_tpu.serving.quota import QuotaExceeded

    n = len(prompts)
    if schedule is not None:
        arrivals = expand_schedule(n, schedule)
        phase_specs = [(float(d), float(r)) for d, r in schedule]
    else:
        if rate_rps is None:
            raise ValueError("run_open_loop needs rate_rps or schedule")
        interval = 1.0 / float(rate_rps)
        arrivals = [(i * interval, 0) for i in range(n)]
        phase_specs = None
    handles = []
    handle_phase = []  # parallel to handles: arrival phase index
    shed = 0
    shed_by_phase: Dict[int, int] = {}
    offered_by_phase: Dict[int, int] = {}
    i = 0
    t0 = time.monotonic()
    while i < len(arrivals) or session.scheduler.has_work():
        now = time.monotonic()
        while i < len(arrivals) and t0 + arrivals[i][0] <= now:
            phase = arrivals[i][1]
            offered_by_phase[phase] = offered_by_phase.get(phase, 0) + 1
            try:
                handles.append(session.submit(
                    prompts[i], max_new_tokens,
                    tenant=tenants[i % len(tenants)],
                    deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s,
                ))
                handle_phase.append(phase)
            except QuotaExceeded:
                shed += 1
                shed_by_phase[phase] = shed_by_phase.get(phase, 0) + 1
            i += 1
        if session.scheduler.has_work():
            session.step(now)
        elif i < len(arrivals):
            time.sleep(max(0.0, min(0.002, t0 + arrivals[i][0] - now)))
    dt = time.monotonic() - t0

    completed_ok = sum(1 for h in handles if h.status == h.DONE)
    missed = sum(1 for h in handles if h.finish_reason == "deadline")
    offered_rps = (
        rate_rps if schedule is None
        else n / sum(d for d, _ in phase_specs)
        if phase_specs and sum(d for d, _ in phase_specs) > 0 else 0.0
    )
    report = {
        "offered_rps": round(float(offered_rps), 2),
        "requests_offered": len(arrivals),
        "accepted": len(handles),
        "shed": shed,
        "completed_ok": completed_ok,
        "deadline_misses": missed,
        "deadline_miss_ratio": round(missed / len(handles), 4)
        if handles else 0.0,
        "goodput_rps": round(completed_ok / dt, 2) if dt > 0 else 0.0,
        "wall_s": round(dt, 4),
    }
    if phase_specs is not None:
        phases = []
        for p, (dur, rate) in enumerate(phase_specs):
            ok = sum(
                1 for h, hp in zip(handles, handle_phase)
                if hp == p and h.status == h.DONE
            )
            phases.append({
                "phase": p,
                "duration_s": dur,
                "rate_rps": rate,
                "offered": offered_by_phase.get(p, 0),
                "shed": shed_by_phase.get(p, 0),
                "completed_ok": ok,
                "goodput_rps": round(ok / dur, 2) if dur > 0 else 0.0,
            })
        report["phases"] = phases
    return report
