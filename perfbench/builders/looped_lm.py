"""serving.LoopedLM through ServingSession: ServeSystem with the model, the
weights' type and the plain reference exchanged, and the counted window's
edges put on the span ring's clock for readers/decode_live_slots.py."""

from __future__ import annotations

import time

import numpy as np

from perfbench import weights
from perfbench.reference import lowprec
from perfbench.serving import ServeSystem


class LoopedServeSystem(ServeSystem):
    def _max_len(self) -> int:
        s = self.cfg["session"]
        return int(s["prefill_buckets"][-1]) + int(s["max_new_limit"])

    def _model(self):
        from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig

        c = self.cfg
        return LoopedLM(LoopedLMConfig(
            vocab=int(c["vocab_size"]), n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
            head_dim=int(c["head_dim"]), d_ff=int(c["intermediate_size"]),
            ut_steps=int(c["total_ut_steps"]), rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
            # rotary positions: no table to size, so the positions the session
            # serves (the configuration's `assumed` says why not the source's)
            max_len=self._max_len(), dtype=c["weights_dtype"],
        ))

    def _weights(self, model=None):
        import jax
        import jax.numpy as jnp

        if not self.shapes:
            model = model or self._model()
            shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            self.shapes = {k: tuple(v.shape) for k, v in shapes.items()}
        return weights.make_weights(
            self.shapes, self.seed, self.cfg["weights"],
            dtype=jnp.dtype(self.cfg["weights_dtype"]),
        )

    # -- the window's edges on the ring's clock ---------------------------------
    def drive(self, schedule, seconds, profiler=None, clock=time.monotonic):
        wall_ns, at = time.time_ns(), clock()   # one pair: the ring's clock and the drive's
        run = super().drive(schedule, seconds, profiler, clock)
        self._window_ns = tuple(
            wall_ns + int((run[k] - at) * 1e9) for k in ("t0", "t_end")
        )
        return run

    def window(self, seconds, profiler, t_process_start):
        out = super().window(seconds, profiler, t_process_start)
        out["facts"]["decode_window_ns"] = self._window_ns
        return out

    # -- the comparison ---------------------------------------------------------
    def gaps(self, sample, cast_name: str = "float32") -> dict:
        """ServeSystem.gaps against reference/looped_lm.py: one reference
        forward over each sampled prompt with its served tokens, a row and a
        layer at a time (one layer's program, run T x L times a row: what
        fits beside the weights and compiles in seconds)."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import looped_lm as ref

        c = self.cfg
        n_heads, ut_steps = int(c["num_attention_heads"]), int(c["total_ut_steps"])
        theta, eps = float(c["rope_theta"]), float(c["rms_norm_eps"])
        n_layers = int(c["num_hidden_layers"])
        t_max, n_max = self._max_len(), int(c["session"]["max_new_limit"])
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
            with jax.default_matmul_precision("highest"):
                layer = jax.jit(lambda p, l, x: ref.one_layer(
                    ref.layer_weights(p, l), x, n_heads, cast, theta, eps)[0])
                close = jax.jit(lambda p, x: ref.final_norm(p, x, eps))
                head = jax.jit(lambda p, x, pos: ref.unembed_at(p, x, pos, cast))
                rows = []
                for i in range(len(sample)):
                    x = ref.embed(params, tokens[i: i + 1])
                    for _ in range(ut_steps):
                        for l in range(n_layers):
                            x = layer(params, l, x)
                        x = close(params, x)
                    rows.append(head(params, x, positions[i: i + 1]))
            return jnp.concatenate(rows)

        ref_logits = run(lowprec.identity)
        best = jnp.max(ref_logits, -1)
        out = {"tokens": int(valid.sum())}
        if cast_name == "float32":
            chosen = jnp.asarray(served)
        else:
            chosen = jnp.argmax(run(lowprec.CASTS[cast_name]), -1)
        got = jnp.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]
        gap = np.asarray(jnp.where(jnp.asarray(valid), best - got, 0.0))
        out["widest_gap"] = float(gap.max())
        out["mean_gap"] = float(gap.sum() / max(1, valid.sum()))
        out["flipped"] = int((gap > 0).sum())
        out["logit_std"] = float(jnp.std(ref_logits[0, 0]))
        return out


def build(cell, seed):
    return LoopedServeSystem(cell, seed)
