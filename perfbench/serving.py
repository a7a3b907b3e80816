"""The serving cells' system under test: ServableLM through ServingSession,
driven by the loop ServingServer's engine thread runs (submit, step), from
one thread, open loop: a request is submitted when it is DUE, whatever the
engine is doing, and every time is counted from the due time.

The benchmark takes its own times: after every session.step() it stamps the
tokens that appeared on each live handle."""

from __future__ import annotations

import gc
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

    def setup(self, say=print) -> None:
        from paddle_tpu.serving.session import ServingSession

        s = self.cfg["session"]
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
    def drive(self, schedule: List[dict], seconds: float, profiler=None) -> dict:
        """Offer `schedule` (due times relative to the start), step the engine
        until every request has finished or DRAIN_S past the close."""
        session = self.session
        clock = time.monotonic
        recs = [
            {"due": r["due"], "prompt": r["prompt"], "max_new": r["max_new"],
             "handle": None, "stamps": [], "started": None, "error": None}
            for r in schedule
        ]
        live: List[dict] = []
        step_spans: List[tuple] = []    # (seconds, ran_prefill, decoded)
        traced_steps: List[tuple] = []  # (step's start, contexts of the slots decoding)
        t_prof = t_prof_stop = None
        backlog_mid = waiting_mid = None
        nxt, n = 0, len(recs)
        t0 = clock()
        t_end = t0 + seconds
        lateness = []
        while True:
            now = clock()
            while nxt < n and t0 + recs[nxt]["due"] <= now:
                r = recs[nxt]
                lateness.append(now - (t0 + r["due"]))
                try:
                    r["handle"] = session.submit(r["prompt"], r["max_new"])
                    live.append(r)
                except Exception as exc:  # shed or refused: counts as failed
                    r["error"] = repr(exc)[:200]
                nxt += 1
            if profiler is not None and profiler.due(now, t_end):
                profiler.start()
                t_prof = now
            if backlog_mid is None and now >= t0 + seconds / 2:
                backlog_mid = len(live)
                waiting_mid = sum(1 for r in live if not r["stamps"])
            if not live:
                if nxt >= n:
                    break
                time.sleep(min(0.001, max(0.0, t0 + recs[nxt]["due"] - clock())))
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
                    continue
                still.append(r)
            live = still
            if profiler is not None and profiler.running and t_b > t_end:
                profiler.stop()
                t_prof_stop = t_b
            step_spans.append((t_b - t_a, ran_prefill, session.decode_steps - decode_before))
        t1 = clock()
        backlog_end = sum(
            1 for r in recs
            if r["handle"] is not None and (not r["stamps"] or r["stamps"][-1] > t_end)
        )
        # of those, the requests with no first token when the window closed
        waiting_end = sum(
            1 for r in recs if r["handle"] is not None
            and (not r["stamps"] or r["stamps"][0] > t_end)
        )
        self.records = recs
        # the reduction keeps the trace's last KEEP_S seconds: so here
        kept_from = (t_prof_stop or t1) - (profiler.KEEP_S if profiler is not None else 0.0)
        return {
            "t0": t0, "t1": t1, "t_end": t_end, "recs": recs, "t_prof": t_prof,
            "step_spans": step_spans,
            "traced_contexts": [c for t, c in traced_steps if t >= kept_from],
            "lateness": lateness, "backlog_mid": backlog_mid or 0,
            "backlog_end": backlog_end, "waiting_mid": waiting_mid or 0,
            "waiting_end": waiting_end,
        }

    def reduce(self, run: dict) -> dict:
        """From stamps to the end-to-end metrics and the readers' facts."""
        recs, t0 = run["recs"], run["t0"]
        worst = run["t1"] - t0 + DRAIN_S
        finished = [r for r in recs if r["handle"] is not None and r["handle"].done
                    and r["handle"].tokens]
        failed = len(recs) - len(finished)
        ttft = arith.ttft_samples(
            [t0 + r["due"] for r in recs],
            [r["stamps"][0] if r["stamps"] and r in finished else None for r in recs],
            worst,
        )
        gaps = [g for r in recs for g in arith.token_gaps(r["stamps"])]
        prompt_tokens = sum(len(r["prompt"]) for r in recs if r["stamps"])
        out_tokens = sum(len(r["stamps"]) for r in recs)
        last = max((r["stamps"][-1] for r in recs if r["stamps"]), default=run["t1"])
        decode_only = [s for s, pre, dec in run["step_spans"] if dec and not pre]
        untraced = None
        if run.get("t_prof") is not None:
            # a traced run's own work and time before the profiler started
            tp = run["t_prof"]
            untraced = {
                "tokens": sum(len(r["prompt"]) for r in recs if r["stamps"] and r["stamps"][0] < tp)
                + sum(1 for r in recs for s in r["stamps"] if s < tp),
                "seconds": tp - t0,
            }
        return {
            "untraced": untraced,
            "attempted": len(recs),
            "failed": failed,
            # the end-to-end pair: what the unluckiest tenth of requests wait
            # for a first token, and what a stall costs, averaged over the
            # longest hundredth of ALL gaps (arith.tail_mean)
            "ttft_tail_ms": 1e3 * arith.tail_mean(ttft, TTFT_TAIL),
            "itl_tail_ms": 1e3 * arith.tail_mean(gaps, ITL_TAIL),
            "ttft_p95_ms": 1e3 * arith.percentile(ttft, 95),
            "ttft_p50_ms": 1e3 * arith.percentile(ttft, 50),
            "itl_p99_ms": 1e3 * arith.percentile(gaps, 99) if gaps else float("nan"),
            "itl_p50_ms": 1e3 * arith.percentile(gaps, 50) if gaps else float("nan"),
            # either side of the 99th: how near it lies to a step in the tail
            "itl_p98_ms": 1e3 * arith.percentile(gaps, 98) if gaps else float("nan"),
            "itl_p995_ms": 1e3 * arith.percentile(gaps, 99.5) if gaps else float("nan"),
            "n_gaps": len(gaps),
            "serve_s": last - t0,
            "prompt_tokens": prompt_tokens,
            "output_tokens": out_tokens,
            "decode_only_step_s": decode_only,
            "lateness_p99_ms": 1e3 * arith.percentile(run["lateness"], 99) if run["lateness"] else 0.0,
            "backlog_mid": run["backlog_mid"],
            "backlog_end": run["backlog_end"],
            "waiting_mid": run["waiting_mid"],
            "waiting_end": run["waiting_end"],
            "traced_contexts": run["traced_contexts"],
            "steps": len(run["step_spans"]),
            # a stall of the host or the engine shows here before it shows in a tail
            "step_max_ms": 1e3 * max((s for s, _, _ in run["step_spans"]), default=0.0),
            "gap_max_ms": 1e3 * max(gaps, default=0.0),
            "prefill_steps": sum(1 for _, pre, _ in run["step_spans"] if pre),
        }

    def window(self, seconds: float, profiler, t_process_start: float) -> dict:
        gen = registry.load_module("traffic", self.wl["generator"])
        schedule = gen.make_schedule(
            self.wl["params"], seconds, self.seed,
            int(self.cfg["vocab_size"]), self.session.cfg.bos_id,
        )
        t_window = time.perf_counter()
        run = self.drive(schedule, seconds, profiler)
        m = self.reduce(run)
        info = [
            f"{m['attempted']} requests due in {seconds:.0f} s, {m['failed']} failed or "
            f"unfinished; ttft p50 {m['ttft_p50_ms']:.1f} ms p95 {m['ttft_p95_ms']:.1f} ms "
            f"tail mean {m['ttft_tail_ms']:.1f} ms; gap tail mean {m['itl_tail_ms']:.1f} ms p50 {m['itl_p50_ms']:.1f} ms p98 {m['itl_p98_ms']:.1f} ms p99 {m['itl_p99_ms']:.1f} ms "
            f"p99.5 {m['itl_p995_ms']:.1f} ms over {m['n_gaps']} gaps",
            f"generator lateness p99 {m['lateness_p99_ms']:.2f} ms; backlog at the middle "
            f"{m['backlog_mid']} ({m['waiting_mid']} with no first token yet), at the close "
            f"{m['backlog_end']} ({m['waiting_end']}); {m['steps']} engine steps, "
            f"{m['prefill_steps']} with a prefill, the longest {m['step_max_ms']:.1f} ms (longest gap "
            f"{m['gap_max_ms']:.1f} ms); {m['prompt_tokens']} prompt and "
            f"{m['output_tokens']} generated tokens in {m['serve_s']:.2f} s",
        ]
        facts = {k: m[k] for k in (
            "serve_s", "prompt_tokens", "output_tokens", "decode_only_step_s",
            "traced_contexts", "steps", "prefill_steps", "backlog_mid", "backlog_end",
            "untraced",
        )}
        facts["queue_wait_s"] = [
            r["started"] - (run["t0"] + r["due"]) for r in run["recs"]
            if r["started"] is not None
        ]
        return {
            "attempted": m["attempted"], "failed": m["failed"],
            "end_to_end": {
                "ttft_tail_ms": m["ttft_tail_ms"], "itl_tail_ms": m["itl_tail_ms"],
                "setup_s": t_window - t_process_start,
            },
            "facts": facts, "info": info,
        }

    def release(self) -> None:
        self.session = None
        gc.collect()

    # -- the comparison ---------------------------------------------------------
    def sample(self) -> List[dict]:
        """Finished requests for the comparison, drawn from the seed, the
        longest (prompt + served tokens) among them."""
        done = [r for r in self.records if r["handle"] is not None
                and r["handle"].done and r["handle"].tokens]
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
        tokens. Returns the widest gap by which a served token's logit lies
        below the reference's best, and for a control (cast_name below
        float32) the widest gap of the token the lower precision puts first."""
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
        never = sum(1 for r in self.records if r["handle"] is None
                    or not r["handle"].done or not r["handle"].tokens)
        if not sample:
            return dict(self.judge({"widest_gap": float("nan")}),
                        never_answered=(float(never), 0.0))
        g = self.gaps(sample)
        say(f"info: compared {g['tokens']} served tokens of {len(sample)} requests: widest "
            f"gap {g['widest_gap']:.5f}, mean {g['mean_gap']:.6f}, {g['flipped']} below the "
            f"reference's best; reference logits' std {g['logit_std']:.3f}")
        return dict(self.judge(g), never_answered=(float(never), 0.0))

    def judge(self, numbers: Dict[str, float]) -> Dict[str, tuple]:
        """The numbers of gaps() beside their limits: what harness.decide
        takes, for the program's readings, the control's and a fault's alike."""
        limit = float(self.wl["check"]["limits"]["token_logit_gap"])
        return {"token_logit_gap": (float(numbers["widest_gap"]), limit)}

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
                "ttft_tail_ms": m["ttft_tail_ms"], "itl_tail_ms": m["itl_tail_ms"]}
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
