"""serving.HybridMoELM through ServingSession: LoopedServeSystem (the window's
edges on the span ring's clock) with the model, the weights and the plain
reference exchanged, and the model's device counters read around the drive
for readers/moe_counters.py."""

from __future__ import annotations

import functools
import zlib

import numpy as np

from perfbench.builders.looped_lm import LoopedServeSystem
from perfbench.reference import lowprec


@functools.lru_cache(maxsize=None)
def _filler(shape, dtype, spec):
    """A jitted `fill(buf, i, key)`: buf[i] drawn as `spec` says, in place
    (buf donated), float32 arithmetic cast to the leaf's type. A layer at a
    time: a stacked leaf of 2 G elements never exists in float32."""
    import jax
    import jax.numpy as jnp

    kind, a, b = spec

    def draw(key):
        if kind == "normal":
            w = a + b * jax.random.normal(key, shape, jnp.float32)
        elif kind == "constant":
            w = jnp.full(shape, a, jnp.float32)
        elif kind == "log_of_uniform":
            w = jnp.log(jax.random.uniform(key, shape, jnp.float32, a, b))
        elif kind == "softplus_inverse_of_log_uniform":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(a), np.log(b)))
            w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(f"no such weight rule: {kind!r}")
        return w.astype(dtype)

    def fill(buf, i, key):
        return buf.at[i].set(draw(jax.random.fold_in(key, i)))

    return jax.jit(fill, donate_argnums=0)


def make_weights(avals: dict, seed: int, rules) -> dict:
    """Every leaf from the seed, on the device, in its own type. `rules` (the
    configuration's `weights`) are tried in order; each may match by name
    suffix and gives a `kind`: normal (mean, std; std "fan_in" is 1/sqrt of
    the second-last dimension), constant (value), log_of_uniform (low,
    high), softplus_inverse_of_log_uniform (low, high)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    out = {}
    for name, aval in sorted(avals.items()):
        rule = next(r for r in rules if name.endswith(r.get("suffix", "")))
        kind = rule["kind"]
        if kind == "normal":
            std = rule["std"]
            spec = (kind, float(rule.get("mean", 0.0)),
                    float(aval.shape[-2]) ** -0.5 if std == "fan_in" else float(std))
        elif kind == "constant":
            spec = (kind, float(rule["value"]), 0.0)
        else:
            spec = (kind, float(rule["low"]), float(rule["high"]))
        leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        # a stacked leaf one layer at a time; a flat one (the embedding) whole
        lead = aval.shape[0] if len(aval.shape) >= 3 else 1
        shape = tuple(aval.shape) if lead > 1 else (1,) + tuple(aval.shape)
        fill = _filler(shape[1:], jnp.dtype(aval.dtype), spec)
        buf = jnp.zeros(shape, aval.dtype)
        for i in range(lead):
            buf = fill(buf, i, leaf_key)
        out[name] = buf.reshape(aval.shape)
    return out


class HybridMoEServeSystem(LoopedServeSystem):
    def _model(self):
        from paddle_tpu.serving.hybrid_moe_lm import HybridMoEConfig, HybridMoELM

        c = self.cfg
        return HybridMoELM(HybridMoEConfig(
            vocab=int(c["vocab_size"]), layer_types=tuple(c["layer_types"]),
            d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["hidden_size"]) // int(c["num_attention_heads"]),
            mamba_heads=int(c["mamba_n_heads"]), mamba_head_dim=int(c["mamba_d_head"]),
            mamba_state=int(c["mamba_d_state"]), mamba_groups=int(c["mamba_n_groups"]),
            mamba_conv=int(c["mamba_d_conv"]), mamba_chunk=int(c["mamba_chunk_size"]),
            num_experts_routed=int(c["num_experts_routed"]),
            experts_held=tuple(c["experts_held"]), top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["intermediate_size"]),
            shared_width=int(c["shared_intermediate_size"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]), rms_eps=float(c["rms_norm_eps"]),
            # no position table to size: the positions the session serves
            max_len=self._max_len(), dtype=c["weights_dtype"],
        ))

    def _weights(self, model=None):
        import jax

        if not self.shapes:
            model = model or self._model()
            self.shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        return make_weights(self.shapes, self.seed, self.cfg["weights"])

    # -- the device counters around the drive -----------------------------------
    def drive(self, schedule, seconds, profiler=None, **kw):
        before = self.session.read_counters()
        run = super().drive(schedule, seconds, profiler, **kw)
        after = self.session.read_counters()
        self._moe_counted = {k: (after[k] - before[k]).tolist() for k in after}
        return run

    def window(self, seconds, profiler, t_process_start):
        out = super().window(seconds, profiler, t_process_start)
        out["facts"]["moe_counted"] = self._moe_counted
        return out

    # -- the comparison ---------------------------------------------------------
    def gaps(self, sample, cast_name: str = "float32") -> dict:
        """ServeSystem.gaps against reference/hybrid_moe_lm.py: one reference
        forward over each sampled prompt with its served tokens, a row and a
        layer at a time (two layer programs, one a kind, the layer an
        argument: what fits beside the weights and compiles in seconds)."""
        import jax
        import jax.numpy as jnp

        from perfbench.reference import hybrid_moe_lm as ref

        c = self.cfg
        kinds = list(c["layer_types"])
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
                layer = {
                    kind: jax.jit(lambda p, x, l, i, kind=kind: ref.one_layer(
                        ref.layer_weights(p, l, i, kind), x, kind, c, cast))
                    for kind in set(kinds)
                }
                head = jax.jit(lambda p, x, pos: ref.unembed_at(p, x, pos, c, cast))
                rows = []
                for i in range(len(sample)):
                    x = ref.embed(params, tokens[i], c)
                    for l, kind in enumerate(kinds):
                        x = layer[kind](params, x, l, kinds[:l].count(kind))
                    rows.append(head(params, x, positions[i]))
            return jnp.stack(rows)

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
        # read, not judged: a tied head over random weights can read the last
        # token back; a model that repeats one token compares nothing
        same = (served[:, 1:] == served[:, :-1]) & valid[:, 1:]
        out["repeat_share"] = float(same.sum() / max(1, valid[:, 1:].sum()))
        self._compared = out
        return out

    def verify(self, say=print):
        self._compared = None
        checks = super().verify(say)
        if self._compared:
            g = self._compared
            say(f"info: {100 * g['repeat_share']:.1f}% of the compared tokens repeat the token "
                f"before them; the reference's best lies {g['widest_gap'] / g['logit_std']:.2f} "
                f"logit standard deviations above the widest-gap token")
        return checks


def build(cell, seed):
    return HybridMoEServeSystem(cell, seed)
