"""serving.WindowMoELM through ServingSession: HybridMoEServeSystem (the
window's edges on the span ring's clock, the expert counters read around
the drive, the weights made a layer at a time) with the model, the set-up and
the plain reference exchanged.

The set-up is ServeSystem's with chunked prefill on: the session gets the
configuration's `prefill_chunk`, and one request per bucket and one prompt a
chunk longer than the largest bucket warm every program the traffic uses.
The reference's positions run to the longest prompt the traffic sends plus
`max_new_limit`, as the model's do, not to the largest bucket's."""

from __future__ import annotations

import numpy as np

from perfbench.builders.hybrid_moe_lm import HybridMoEServeSystem
from perfbench.reference import lowprec

# the window a fault reading gives the window layers: past every position
NO_WINDOW = 1 << 30


class WindowMoEServeSystem(HybridMoEServeSystem):
    def _max_len(self) -> int:
        return (int(self.wl["params"]["prompt_len"]["max"])
                + int(self.cfg["session"]["max_new_limit"]))

    def _model(self):
        from paddle_tpu.serving.window_moe_lm import WindowMoEConfig, WindowMoELM

        c = self.cfg
        d = int(c["hidden_size"])
        return WindowMoELM(WindowMoEConfig(
            vocab=int(c["vocab_size"]), layer_types=tuple(c["layer_types"]), d_model=d,
            n_heads=int(c["num_attention_heads"]), n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), window=int(c["sliding_window"]),
            rope_theta=float(c["rope_theta"]), n_dense=int(c["num_dense_layers"]),
            dense_width=int(c["intermediate_size"]), num_experts=int(c["num_experts"]),
            top_k=int(c["num_experts_per_tok"]), expert_width=int(c["moe_intermediate_size"]),
            shared_width=int(c["num_shared_experts"]) * int(c["moe_intermediate_size"]),
            route_scale=float(c["route_scale"]),
            embedding_scale=float(d) ** 0.5 if c["mup_enabled"] else 1.0,
            rms_eps=float(c["rms_norm_eps"]), max_len=self._max_len(),
            dtype=c["weights_dtype"],
        ))

    def setup(self, say=print) -> None:
        from paddle_tpu.serving.session import ServingSession

        s = self.cfg["session"]
        model = self._model()
        self.session = ServingSession(
            model, self._weights(model),
            max_slots=int(s["max_slots"]), page_size=int(s["page_size"]),
            num_pages=int(s["num_pages"]), prefill_buckets=tuple(s["prefill_buckets"]),
            prefill_chunk=int(s["prefill_chunk"]), max_new_limit=int(s["max_new_limit"]),
            max_queue=int(s["max_queue"]),
        )
        # every program the traffic uses: a prompt of each bucket's length
        # (prefill and commit), one a token past the largest bucket (the
        # chunk program, twice: a first chunk and a last), the decode step
        rs = np.random.default_rng(self.seed + 7)
        vocab, bos = int(self.cfg["vocab_size"]), self.session.cfg.bos_id
        lengths = list(self.session.buckets) + [self.session.buckets[-1] + 1]
        for n in lengths:
            self.session.submit([bos] + [int(t) for t in rs.integers(3, vocab, n - 1)], 2)
        self.session.run_until_idle()
        say(f"info: warmed prefill buckets {list(self.session.buckets)}, the "
            f"{self.session.prefill_chunk}-token chunk and the decode step")

    # -- the comparison ---------------------------------------------------------
    def gaps(self, sample, cast_name: str = "float32", window: int = 0) -> dict:
        """ServeSystem.gaps against reference/window_moe_lm.py: one reference
        forward over each sampled prompt with its served tokens, a layer and
        a block of rows at a time, the sequence padded to the longest the
        traffic sends (one program a kind of layer for every request), and a
        request's logits reduced to what is compared before the next one's
        (1.2 GB each over the 200,192 tokens). `window` > 0: the
        reference's window layers take it in place of the configuration's
        (a fault reading: its chosen tokens judged against the reference's)."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import window_moe_lm as ref

        c = self.cfg
        t_max = -(-self._max_len() // ref.ROWS) * ref.ROWS
        n_max = int(c["session"]["max_new_limit"])
        params = self._weights()
        served = np.zeros((len(sample), n_max), np.int32)
        valid = np.zeros((len(sample), n_max), bool)
        gaps, std = [], None
        with jax.default_matmul_precision("highest"):
            for i, r in enumerate(sample):
                toks = [int(t) for t in r["handle"].tokens]
                seq = r["prompt"] + toks
                tokens = np.zeros(t_max, np.int32)
                tokens[: len(seq)] = seq
                positions = np.zeros(n_max, np.int32)
                positions[: len(toks)] = np.arange(len(toks)) + len(r["prompt"]) - 1
                served[i, : len(toks)] = toks
                valid[i, : len(toks)] = True

                def logits(cast, cfg):
                    return ref.logits_at(params, jnp.asarray(tokens), len(seq) - 1,
                                         jnp.asarray(positions), cfg, cast)

                if window:
                    chosen = jnp.argmax(logits(lowprec.identity, dict(c, sliding_window=window)), -1)
                elif cast_name == "float32":
                    chosen = jnp.asarray(served[i])
                else:
                    chosen = jnp.argmax(logits(lowprec.CASTS[cast_name], c), -1)
                want = logits(lowprec.identity, c)
                got = jnp.take_along_axis(want, chosen[:, None], -1)[:, 0]
                gaps.append(np.asarray(jnp.where(jnp.asarray(valid[i]), jnp.max(want, -1) - got, 0.0)))
                std = float(jnp.std(want[0])) if std is None else std
                del want
        gap = np.stack(gaps)
        out = {"tokens": int(valid.sum())}
        out["widest_gap"] = float(gap.max())
        out["mean_gap"] = float(gap.sum() / max(1, valid.sum()))
        out["flipped"] = int((gap > 0).sum())
        out["logit_std"] = std
        same = (served[:, 1:] == served[:, :-1]) & valid[:, 1:]
        out["repeat_share"] = float(same.sum() / max(1, valid[:, 1:].sum()))
        # read, not judged: the compared tokens that lie past the window
        out["past_window"] = int(sum(
            max(0, min(len(r["handle"].tokens), len(r["prompt"]) + len(r["handle"].tokens) - 1
                       - int(c["sliding_window"]))) for r in sample))
        self._compared = out
        return out

    # -- tools: readings for the limits -------------------------------------------
    def calibrate(self, window_s=10.0, program=True, control=False, faults=False):
        """HybridMoEServeSystem.calibrate's readings over a shorter drive, and
        where `faults`, the reference with its window layers unwindowed
        taking the program's place (a program that ignores the window)
        before the altered token. The drive: twice `sample_requests` clients
        from the plan's start, no lead-in, a window of `window_s`, then the
        drain. The numbers compared are a request's own (a slot's tokens do
        not depend on its batch), and the plan's first 16 requests hold
        three prompts of 16k; the cell's lead-in of 96 would cost a minute
        a seed."""
        full = self.wl
        params = dict(full["params"], lead_in_finished=0,
                      clients=2 * int(full["check"]["sample_requests"]))
        self.wl = dict(full, params=params)
        try:
            yield from super().calibrate(window_s, program, control, faults=False)
        finally:
            self.wl = full
        if faults:
            sample = self.sample()
            yield {"who": "fault:window_ignored", "numbers": self.gaps(sample, window=NO_WINDOW)}
            for r in sample[:1]:
                toks = r["handle"].tokens
                toks[len(toks) // 2] = (int(toks[len(toks) // 2]) + 1) % int(self.cfg["vocab_size"])
            yield {"who": "fault:token_altered", "numbers": self.gaps(sample)}


def build(cell, seed):
    return WindowMoEServeSystem(cell, seed)
