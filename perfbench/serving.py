"""The serving cells' system under test: ServableLM through ServingSession,
driven by the loop ServingServer's engine thread runs (submit, step), from
one thread. In an open loop (a schedule with due times) a request is
submitted when it is DUE, whatever the engine is doing, and every time is
counted from the due time. In a closed loop (the cell's `clients`; a plan
without due times) the next request of the plan is submitted when a handle
is done, until the close, and a request is due when it is submitted. A
closed loop's counted window opens after an untimed lead-in (the cell's
`lead_in_finished`: that many requests of the plan have finished, so the
empty pool's first cohort has turned over), and at the close the requests
that have no first token yet are cancelled as their clients would: only
those already decoding are drained.

The benchmark takes its own times: after every session.step() it stamps the
tokens that appeared on each live handle."""

from __future__ import annotations

import gc
import statistics
import time
from typing import Dict, List

import numpy as np

from perfbench import arith, registry, weights
from perfbench.reference import lowprec

DRAIN_S = 60.0  # wait this long past the close for answers that are late
TTFT_TAIL = 0.10  # ttft_tail_ms: mean of the slowest tenth of requests
ITL_TAIL = 0.01   # itl_tail_ms: mean of the longest hundredth of gaps


class ServeSystem:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.wl = cell.config, cell.workload
        self.session = None
        self.shapes: Dict = {}
        self.records: List[dict] = []
        self._t0 = float("-inf")  # when the last drive's counted window opened
        self._precision_before = None

    # -- set-up ---------------------------------------------------------------
    def _model(self):
        from paddle_tpu.serving.model import LMConfig, ServableLM

        c = self.cfg
        return ServableLM(LMConfig(
            vocab=int(c["vocab_size"]), n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
            max_len=int(c["max_position_embeddings"]),
        ))

    def _weights(self, model=None):
        import jax

        if not self.shapes:
            model = model or self._model()
            shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            self.shapes = {k: tuple(v.shape) for k, v in shapes.items()}
        return weights.make_weights(self.shapes, self.seed, self.cfg["weights"])

    def _set_precision(self, name) -> None:
        """The configuration's `matmul_precision` (jax's names: "high" is
        three bfloat16 passes) for every product the program traces without
        a precision of its own; None restores what the process had."""
        import jax

        if name is None:
            if self._precision_before is not None:
                jax.config.update("jax_default_matmul_precision", self._precision_before[0])
                self._precision_before = None
            return
        self._precision_before = (jax.config.jax_default_matmul_precision,)
        jax.config.update("jax_default_matmul_precision", name)

    def setup(self, say=print) -> None:
        from paddle_tpu.serving.session import ServingSession

        s = self.cfg["session"]
        self._set_precision(self.cfg.get("matmul_precision"))
        model = self._model()
        self.session = ServingSession(
            model, self._weights(model),
            max_slots=int(s["max_slots"]), page_size=int(s["page_size"]),
            num_pages=int(s["num_pages"]),
            prefill_buckets=tuple(s["prefill_buckets"]),
            max_new_limit=int(s["max_new_limit"]), max_queue=int(s["max_queue"]),
        )
        # warm every shape the traffic uses: one request per prefill bucket
        # (prefill + commit at that bucket) and the one decode executable
        rs = np.random.default_rng(self.seed + 7)
        vocab, bos = int(self.cfg["vocab_size"]), self.session.cfg.bos_id
        for bucket in self.session.buckets:
            prompt = [bos] + [int(t) for t in rs.integers(3, vocab, bucket - 1)]
            self.session.submit(prompt, 2)
        self.session.run_until_idle()
        say(f"info: warmed prefill buckets {list(self.session.buckets)} and the decode step")

    # -- the measured window --------------------------------------------------
    def drive(self, schedule: List[dict], seconds: float, profiler=None,
              clock=time.monotonic) -> dict:
        """Offer `schedule`, step the engine until every request submitted
        has finished or DRAIN_S past the close. Open loop: each request at
        its due time (relative to the start), the window opens at once, and
        whatever was due in it is drained. Closed loop: `clients` requests
        outstanding, the next of the plan after the step() that finished
        one, none after the close; the window of `seconds` opens after the
        step in which the `lead_in_finished`-th request finished (untimed,
        set-up's), and at the close the requests with no first token are
        cancelled, so the drain is of those already decoding. The order of
        submissions, the engine step each falls behind and the engine's
        state when the window opens depend on the plan and the engine, not
        on `clock`."""
        session = self.session
        params = self.wl["params"]
        clients = int(params.get("clients", 0))  # 0: an open loop
        lead_in = int(params.get("lead_in_finished", 0)) if clients else 0
        recs = [
            {"due": r.get("due"), "prompt": r["prompt"], "max_new": r["max_new"],
             "handle": None, "stamps": [], "started": None, "error": None,
             "cancelled": False,
             # engine steps before the submit / the finish: read by tests only
             "step": None, "done_step": None}
            for r in schedule
        ]
        live: List[dict] = []
        step_spans: List[tuple] = []    # (seconds, ran_prefill, decoded, the step's end)
        traced_steps: List[tuple] = []  # (step's start, contexts of the slots decoding)
        t_prof = t_prof_stop = None
        backlog_mid = waiting_mid = None
        nxt, n, n_done, closed = 0, len(recs), 0, False
        origin = clock()                 # due times count from here
        t0 = None if lead_in else origin  # the counted window: (t0, t_end]
        t_end = float("inf") if lead_in else origin + seconds
        lateness = []
        while True:
            now = clock()
            while nxt < n and (
                (len(live) < clients and now < t_end) if clients
                else origin + recs[nxt]["due"] <= now
            ):
                r = recs[nxt]
                if clients:
                    r["due"] = now - origin  # its client is free now: due at the submit
                r["step"] = len(step_spans)
                lateness.append(now - (origin + r["due"]))
                try:
                    r["handle"] = session.submit(r["prompt"], r["max_new"])
                    live.append(r)
                except Exception as exc:  # shed or refused: counts as failed
                    r["error"] = repr(exc)[:200]
                nxt += 1
            if clients and nxt >= n and now < t_end:
                raise RuntimeError(
                    f"the plan's {n} requests ran out before the close"
                    f"{'' if t0 is None else f' ({t_end - now:.1f} s before it)'}: "
                    "a longer plan, not a wrapped one")
            if clients and now >= t_end and not closed:
                # the close: a client whose request has no first token gives
                # it up (the handle's own cancel); those decoding are drained
                closed = True
                for r in live:
                    if not r["stamps"]:
                        r["cancelled"] = bool(r["handle"].cancel())
            if profiler is not None and profiler.due(now, t_end):
                profiler.start()
                t_prof = now
            if backlog_mid is None and t0 is not None and now >= t0 + seconds / 2:
                backlog_mid = len(live)
                waiting_mid = sum(1 for r in live if not r["stamps"])
            live = [r for r in live if not (r["cancelled"] and r["handle"].done)]
            if not live:
                if nxt >= n or (clients and now >= t_end):
                    break
                time.sleep(0.001 if clients
                           else min(0.001, max(0.0, origin + recs[nxt]["due"] - clock())))
                continue
            if now > t_end + DRAIN_S:
                break
            if profiler is not None and profiler.running:
                traced_steps.append((clock(), [
                    int(act.next_pos) for _, act in session.scheduler.active_slots()
                    if not act.prefilling
                ]))
            decode_before = session.decode_steps
            t_a = clock()
            session.step()
            t_b = clock()
            ran_prefill = False
            still = []
            admitted = {id(act.handle): act.t_started
                        for _, act in session.scheduler.active_slots()}
            for r in live:
                if r["started"] is None:
                    # the engine's own stamp of the admission (the step's
                    # one clock read), on the clock this loop uses
                    r["started"] = admitted.get(id(r["handle"]))
                h = r["handle"]
                new = len(h.tokens) - len(r["stamps"])
                if new:
                    if not r["stamps"]:
                        ran_prefill = True
                    r["stamps"].extend([t_b] * new)
                if h.done:
                    r["done_step"] = len(step_spans)
                    n_done += 1
                    continue
                still.append(r)
            live = still
            if profiler is not None and profiler.running and t_b > t_end:
                profiler.stop()
                t_prof_stop = t_b
            step_spans.append((t_b - t_a, ran_prefill, session.decode_steps - decode_before, t_b))
            if t0 is None and n_done >= lead_in:
                # the lead-in's last step: the window opens behind it
                t0, t_end = t_b, t_b + seconds
        t1 = clock()
        recs = recs[:nxt]  # a closed loop submits as much of its plan as the window takes
        backlog_end = sum(
            1 for r in recs
            if r["handle"] is not None and (not r["stamps"] or r["stamps"][-1] > t_end)
        )
        # of those, the requests with no first token when the window closed
        waiting_end = sum(
            1 for r in recs if r["handle"] is not None
            and (not r["stamps"] or r["stamps"][0] > t_end)
        )
        self.records, self._t0 = recs, t0
        # the reduction keeps the trace's last KEEP_S seconds: so here
        kept_from = (t_prof_stop or t1) - (profiler.KEEP_S if profiler is not None else 0.0)
        return {
            "origin": origin, "t0": t0, "t1": t1, "t_end": t_end, "recs": recs,
            "t_prof": t_prof, "step_spans": step_spans,
            "traced_contexts": [c for t, c in traced_steps if t >= kept_from],
            "lateness": lateness, "backlog_mid": backlog_mid or 0,
            "backlog_end": backlog_end, "waiting_mid": waiting_mid or 0,
            "waiting_end": waiting_end,
        }

    def reduce(self, run: dict) -> dict:
        """From stamps to the end-to-end metrics and the readers' facts. The
        counted window is (t0, t_end]: a closed loop's lead-in before it and
        the drain after it are driven and stamped, and count nothing."""
        origin, t0, t_end = run.get("origin", run["t0"]), run["t0"], run["t_end"]
        cancelled = [r for r in run["recs"] if r.get("cancelled")]
        recs = [r for r in run["recs"] if not r.get("cancelled")]
        worst = run["t1"] - origin + DRAIN_S
        finished = [r for r in recs if r["handle"] is not None and r["handle"].done
                    and r["handle"].tokens]
        failed = len(recs) - len(finished)
        ttft = arith.ttft_samples(
            [origin + r["due"] for r in recs],
            [r["stamps"][0] if r["stamps"] and r in finished else None for r in recs],
            worst,
        )
        gaps = [g for r in recs for g in arith.token_gaps(r["stamps"])]
        # what the window completed: generated tokens stamped after it opened
        # and at or before the close (stamps rise), first tokens included,
        # prompt tokens not; a request the close cuts counts the tokens it had
        in_window = [
            r["stamps"][arith.count_until(r["stamps"], t0): arith.count_until(r["stamps"], t_end)]
            for r in recs
        ]
        window_tokens = sum(len(stamps) for stamps in in_window)
        # a gap belongs to the window in which it ENDED
        window_gaps = [b - a for r in recs for a, b in zip(r["stamps"], r["stamps"][1:])
                       if t0 < b <= t_end]
        # the work since the window opened, drain included: the readers' facts
        since = [r["stamps"][arith.count_until(r["stamps"], t0):] for r in recs]
        prompts_since = [len(r["prompt"]) if st and st[0] == r["stamps"][0] else 0
                         for r, st in zip(recs, since)]
        last = max((r["stamps"][-1] for r in recs if r["stamps"]), default=run["t1"])
        # the engine steps that ended in the window: the lead-in's, on a pool
        # that fills, and the drain's, with fewer and fewer slots live, are
        # no part of what the window measured
        steps = [s for s in run["step_spans"] if t0 < s[3] <= t_end]
        decode_only = [s for s, pre, dec, _ in steps if dec and not pre]
        untraced = None
        if run.get("t_prof") is not None:
            # a traced run's own work and time from the window's opening to
            # the profiler's start
            tp = run["t_prof"]
            untraced = {
                "tokens": sum(n for n, st in zip(prompts_since, since) if st and st[0] < tp)
                + sum(1 for st in since for s in st if s < tp),
                "seconds": tp - t0,
            }
        fifth = (t_end - t0) / 5.0
        return {
            "untraced": untraced,
            # the requests the run waited for: all it submitted, less those
            # their clients gave up at the close with no first token
            "attempted": len(recs),
            "cancelled_at_close": len(cancelled),
            "failed": failed,
            "lead_in_s": t0 - origin,
            # the end-to-end metric: all generated tokens completed in the
            # window over the whole window, per chip
            "serve_throughput": arith.rate(window_tokens, t_end - t0, self.cell.chips),
            "window_tokens": window_tokens,
            "finished_in_window": sum(1 for r in finished if t0 < r["stamps"][-1] <= t_end),
            # read, not judged: tokens by fifths of the window (does the rate
            # still fall inside it?)
            "tokens_by_fifth": [
                sum(1 for st in in_window for s in st
                    if t0 + k * fifth < s <= t0 + (k + 1) * fifth) for k in range(5)
            ],
            # read, not judged: what the unluckiest tenth of requests wait
            # for a first token, and what a stall costs, averaged over the
            # longest hundredth of ALL gaps (arith.tail_mean)
            "ttft_tail_ms": 1e3 * arith.tail_mean(ttft, TTFT_TAIL),
            "itl_tail_ms": 1e3 * arith.tail_mean(gaps, ITL_TAIL),
            "ttft_p95_ms": 1e3 * arith.percentile(ttft, 95),
            "ttft_p50_ms": 1e3 * arith.percentile(ttft, 50),
            "itl_p99_ms": 1e3 * arith.percentile(gaps, 99) if gaps else float("nan"),
            "itl_p50_ms": 1e3 * arith.percentile(gaps, 50) if gaps else float("nan"),
            # read, not judged: the mean of the gaps that ended by the close
            "tpot_mean_ms": 1e3 * statistics.fmean(window_gaps) if window_gaps else float("nan"),
            # either side of the 99th: how near it lies to a step in the tail
            "itl_p98_ms": 1e3 * arith.percentile(gaps, 98) if gaps else float("nan"),
            "itl_p995_ms": 1e3 * arith.percentile(gaps, 99.5) if gaps else float("nan"),
            "n_gaps": len(gaps),
            "serve_s": last - t0,
            "prompt_tokens": sum(prompts_since),
            "output_tokens": sum(len(st) for st in since),
            "decode_only_step_s": decode_only,
            "lateness_p99_ms": 1e3 * arith.percentile(run["lateness"], 99) if run["lateness"] else 0.0,
            "backlog_mid": run["backlog_mid"],
            "backlog_end": run["backlog_end"],
            "waiting_mid": run["waiting_mid"],
            "waiting_end": run["waiting_end"],
            "traced_contexts": run["traced_contexts"],
            "steps": len(steps),
            # a stall of the host or the engine shows here before it shows in a tail
            "step_max_ms": 1e3 * max((s[0] for s in steps), default=0.0),
            "gap_max_ms": 1e3 * max(gaps, default=0.0),
            "prefill_steps": sum(1 for s in steps if s[1]),
        }

    def window(self, seconds: float, profiler, t_process_start: float) -> dict:
        gen = registry.load_module("traffic", self.wl["generator"])
        schedule = gen.make_schedule(
            self.wl["params"], seconds, self.seed,
            int(self.cfg["vocab_size"]), self.session.cfg.bos_id,
        )
        t_drive = time.perf_counter()
        run = self.drive(schedule, seconds, profiler)
        m = self.reduce(run)
        info = [
            f"serve_throughput {m['serve_throughput']:.2f} tokens/s/chip: {m['window_tokens']} "
            f"generated tokens stamped in the window, {m['finished_in_window']} requests "
            f"finished in it; mean gap between tokens in it (tpot) {m['tpot_mean_ms']:.3f} ms; "
            f"tokens by fifths of the window {m['tokens_by_fifth']}",
            f"lead-in {m['lead_in_s']:.2f} s before the window (set-up's); "
            f"{m['cancelled_at_close']} requests with no first token cancelled at the close; "
            f"the drain after it {run['t1'] - run['t_end']:.2f} s",
            f"{m['attempted']} requests waited for, {m['failed']} failed or "
            f"unfinished; ttft p50 {m['ttft_p50_ms']:.1f} ms p95 {m['ttft_p95_ms']:.1f} ms "
            f"tail mean {m['ttft_tail_ms']:.1f} ms; gap tail mean {m['itl_tail_ms']:.1f} ms p50 {m['itl_p50_ms']:.1f} ms p98 {m['itl_p98_ms']:.1f} ms p99 {m['itl_p99_ms']:.1f} ms "
            f"p99.5 {m['itl_p995_ms']:.1f} ms over {m['n_gaps']} gaps",
            f"generator lateness p99 {m['lateness_p99_ms']:.2f} ms; backlog at the middle "
            f"{m['backlog_mid']} ({m['waiting_mid']} with no first token yet), at the close "
            f"{m['backlog_end']} ({m['waiting_end']}); {m['steps']} engine steps in the window, "
            f"{m['prefill_steps']} with a prefill, the longest {m['step_max_ms']:.1f} ms (longest gap "
            f"{m['gap_max_ms']:.1f} ms); {m['prompt_tokens']} prompt and "
            f"{m['output_tokens']} generated tokens in {m['serve_s']:.2f} s",
        ]
        facts = {k: m[k] for k in (
            "serve_s", "prompt_tokens", "output_tokens", "decode_only_step_s",
            "traced_contexts", "steps", "prefill_steps", "backlog_mid", "backlog_end",
            "untraced",
        )}
        # of the requests admitted since the window opened
        facts["queue_wait_s"] = [
            r["started"] - (run["origin"] + r["due"]) for r in run["recs"]
            if r["started"] is not None and r["started"] >= run["t0"]
        ]
        return {
            "attempted": m["attempted"], "failed": m["failed"],
            "end_to_end": {
                "serve_throughput": m["serve_throughput"],
                # a closed loop's lead-in is set-up: the window opens behind it
                "setup_s": t_drive - t_process_start + m["lead_in_s"],
            },
            "facts": facts, "info": info,
        }

    def release(self) -> None:
        self.session = None
        self._set_precision(None)
        gc.collect()

    # -- the comparison ---------------------------------------------------------
    def sample(self) -> List[dict]:
        """Requests finished since the window opened (a closed loop's lead-in
        is not the window's) for the comparison, drawn from the seed, the
        longest (prompt + served tokens) among them."""
        done = [r for r in self.records if r["handle"] is not None and not r["cancelled"]
                and r["handle"].done and r["handle"].tokens and r["stamps"][-1] > self._t0]
        if not done:
            return []
        n = int(self.wl["check"]["sample_requests"])
        longest = max(done, key=lambda r: len(r["prompt"]) + len(r["handle"].tokens))
        rest = [r for r in done if r is not longest]
        rs = np.random.default_rng(self.seed + 11)
        picks = [rest[i] for i in rs.permutation(len(rest))[: max(0, n - 1)]]
        return [longest] + picks

    def gaps(self, sample: List[dict], cast_name: str = "float32") -> dict:
        """One reference forward over each sampled prompt with its served
        tokens. Returns the widest and the mean gap by which a served token's
        logit lies below the reference's best, and for a control (cast_name
        below float32) those of the token the lower precision puts first."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import lm

        c = self.cfg
        n_layers, n_heads = int(c["num_hidden_layers"]), int(c["num_attention_heads"])
        t_max = int(c["session"]["prefill_buckets"][-1]) + int(c["session"]["max_new_limit"])
        n_max = int(c["session"]["max_new_limit"])
        params = self._weights()
        tokens = np.zeros((len(sample), t_max), np.int32)
        positions = np.zeros((len(sample), n_max), np.int32)
        served = np.zeros((len(sample), n_max), np.int32)
        valid = np.zeros((len(sample), n_max), bool)
        for i, r in enumerate(sample):
            toks = [int(t) for t in r["handle"].tokens]
            seq = r["prompt"] + toks
            tokens[i, : len(seq)] = seq
            positions[i, : len(toks)] = np.arange(len(toks)) + len(r["prompt"]) - 1
            served[i, : len(toks)] = toks
            valid[i, : len(toks)] = True

        def run(cast):
            @jax.jit
            def f(p, tok, pos):
                with jax.default_matmul_precision("highest"):
                    return lm.logits_at(p, tok, pos, n_layers, n_heads, cast)
            # one row at a time: the activations of one long sequence fit
            return jnp.concatenate([
                f(params, tokens[i: i + 1], positions[i: i + 1]) for i in range(len(sample))
            ])

        ref = run(lowprec.identity)
        best = jnp.max(ref, -1)
        out = {"tokens": int(valid.sum())}
        if cast_name == "float32":
            got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
        else:
            low = run(lowprec.CASTS[cast_name])
            first = jnp.argmax(low, -1)
            got = jnp.take_along_axis(ref, first[..., None], -1)[..., 0]
        gap = np.asarray(jnp.where(jnp.asarray(valid), best - got, 0.0))
        out["widest_gap"] = float(gap.max())
        out["mean_gap"] = float(gap.sum() / max(1, valid.sum()))
        out["flipped"] = int((gap > 0).sum())
        out["logit_std"] = float(jnp.std(ref[0, 0]))
        return out

    def verify(self, say=print) -> Dict[str, tuple]:
        sample = self.sample()
        never = sum(1 for r in self.records if not r["cancelled"] and (
            r["handle"] is None or not r["handle"].done or not r["handle"].tokens))
        if not sample:
            return dict(self.judge({"widest_gap": float("nan")}),
                        never_answered=(float(never), 0.0))
        g = self.gaps(sample)
        say(f"info: compared {g['tokens']} served tokens of {len(sample)} requests: widest "
            f"gap {g['widest_gap']:.5f}, mean {g['mean_gap']:.6f}, {g['flipped']} below the "
            f"reference's best; reference logits' std {g['logit_std']:.3f}")
        return dict(self.judge(g), never_answered=(float(never), 0.0))

    # the cell's limits, by the number of gaps() each one holds
    COMPARED = {"token_logit_gap": "widest_gap", "token_logit_gap_mean": "mean_gap"}

    def judge(self, numbers: Dict[str, float]) -> Dict[str, tuple]:
        """The numbers of gaps() beside their limits: what harness.decide
        takes, for the program's readings, the control's and a fault's alike."""
        return {
            name: (float(numbers.get(self.COMPARED[name], float("nan"))), float(limit))
            for name, limit in self.wl["check"]["limits"].items()
        }

    # -- tools: readings for the limits, and the rate sweep ---------------------
    def calibrate(self, window_s=10.0, program=True, control=False, faults=False):
        self.setup(say=lambda *_: None)
        gen = registry.load_module("traffic", self.wl["generator"])
        schedule = gen.make_schedule(
            self.wl["params"], window_s, self.seed,
            int(self.cfg["vocab_size"]), self.session.cfg.bos_id,
        )
        m = self.reduce(self.drive(schedule, window_s))
        self.release()
        sample = self.sample()
        base = {"requests": m["attempted"], "failed": m["failed"],
                "serve_throughput": m["serve_throughput"], "drained_s": m["serve_s"] - window_s,
                **{k: m[k] for k in ("lead_in_s", "cancelled_at_close", "tokens_by_fifth",
                                     "steps", "prefill_steps", "finished_in_window")},
                "decode_step_ms_p50": 1e3 * float(np.median(m["decode_only_step_s"]))
                if m["decode_only_step_s"] else None}
        if program:
            yield dict(base, who="program", numbers=self.gaps(sample))
        if control:
            control = self.wl["check"]["control"]
            yield {"who": "control:" + control, "numbers": self.gaps(sample, control)}
        if faults:
            for r in sample[:1]:
                toks = r["handle"].tokens
                toks[len(toks) // 2] = (int(toks[len(toks) // 2]) + 1) % int(self.cfg["vocab_size"])
            yield {"who": "fault:token_altered", "numbers": self.gaps(sample)}

    def sweep(self, rates, seconds: float):
        """One session, one window per rate: the knee is the highest rate at
        which the backlog at the close is no larger than at the middle and
        no request is shed."""
        self.setup(say=lambda *_: None)
        gen = registry.load_module("traffic", self.wl["generator"])
        for rate in rates:
            params = dict(self.wl["params"], rate_per_s=float(rate))
            schedule = gen.make_schedule(
                params, seconds, self.seed, int(self.cfg["vocab_size"]),
                self.session.cfg.bos_id,
            )
            m = self.reduce(self.drive(schedule, seconds))
            yield {"rate_per_s": rate, **{k: m[k] for k in (
                "attempted", "failed", "ttft_p50_ms", "ttft_p95_ms", "ttft_tail_ms",
                "itl_p50_ms", "itl_p99_ms", "itl_tail_ms", "backlog_mid", "backlog_end",
                "waiting_mid", "waiting_end", "serve_s",
                "prompt_tokens", "output_tokens", "lateness_p99_ms", "steps", "prefill_steps")},
                "decode_step_ms_p50": 1e3 * float(np.median(m["decode_only_step_s"])) if m["decode_only_step_s"] else None}
        self.release()
