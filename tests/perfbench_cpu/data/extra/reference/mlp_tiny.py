"""Plain reference of the two-layer classifier: tanh(x W1 + b1) W2 + b2,
softmax cross-entropy averaged over the rows."""

import jax
import jax.numpy as jnp
from jax import lax


def make_loss(config, cast):
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=lax.Precision.HIGHEST)

    def loss(p, batch):
        h = jnp.tanh(mm(batch["x"], p["h.w"]) + p["h.b"])
        logits = mm(h, p["logits.w"]) + p["logits.b"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, batch["label"].astype(jnp.int32)[:, None], -1))

    return loss
