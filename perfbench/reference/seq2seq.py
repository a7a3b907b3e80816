"""Attention NMT (Bahdanau et al. 2014, arXiv:1409.0473, as the reference's
demo/seqToseq composes it): forward pass and loss in plain jax.numpy, f32.

Encoder: source embedding; two GRUs (forward, and backward over reversed
time), each fed by a linear projection of the embedding to 3H (gates in the
order update z, reset r, candidate c); their states concatenated to 2H.
GRU step (GruCompute semantics: the reset gate applies to the recurrent
candidate term):  z = sigmoid(x_z + h W_z), r = sigmoid(x_r + h W_r),
c = tanh(x_c + (r * h) W_c), h' = (1 - z) h + z c.
Decoder: h0 = tanh(enc[:, 0] W_init); at step t the PREVIOUS state attends
over the encoder states with additive attention v . tanh(enc W_e + h W_d),
softmax over source positions, and the GRU input is [target embedding_t,
context_t] W_in. Logits = h_t W_out + b; the loss is the softmax
cross-entropy summed over target positions and averaged over the pairs.
Every sequence of the cell has full length, so there is no masking.

Parameters (flat dict): src_emb.w, enc.<fw|bw>.input_proj.<w|b>,
enc.<fw|bw>.<w_hzr|w_hc|b>, trg_emb_table, decoder.att.<w_enc|w_dec|v>,
decoder.w_in, decoder.gru.<w_hzr|w_hc|b>, decoder.w_init, out_w, out_b."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def make_loss(config: dict, cast):
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=HI)

    def gru_step(x_t, h, w_hzr, w_hc, b):
        hdim = h.shape[-1]
        xz, xr, xc = jnp.split(x_t + b, 3, axis=-1)
        rz = mm(h, w_hzr)
        z = jax.nn.sigmoid(xz + rz[:, :hdim])
        r = jax.nn.sigmoid(xr + rz[:, hdim:])
        c = jnp.tanh(xc + mm(r * h, w_hc))
        return (1.0 - z) * h + z * c

    def gru(p, name, emb, reverse):
        proj = mm(emb, p[f"{name}.input_proj.w"]) + p[f"{name}.input_proj.b"]
        h0 = jnp.zeros((emb.shape[0], proj.shape[-1] // 3), jnp.float32)

        def step(h, x_t):
            h = gru_step(x_t, h, p[f"{name}.w_hzr"], p[f"{name}.w_hc"], p[f"{name}.b"])
            return h, h

        _, hs = lax.scan(step, h0, jnp.swapaxes(proj, 0, 1), reverse=reverse)
        return jnp.swapaxes(hs, 0, 1)

    def decoder(p, enc, emb):
        d_emb = emb.shape[-1]
        enc_proj = mm(enc, p["decoder.att.w_enc"])
        h0 = jnp.tanh(mm(enc[:, 0], p["decoder.w_init"]))
        proj_emb = mm(emb, p["decoder.w_in"][:d_emb])

        @jax.checkpoint
        def step(h, pe_t):
            q = mm(h, p["decoder.att.w_dec"])
            e = jnp.tanh(enc_proj + q[:, None, :])
            scores = jnp.einsum("bta,a->bt", cast(e), cast(p["decoder.att.v"]), precision=HI)
            w = jax.nn.softmax(scores, axis=1)
            context = jnp.einsum("btd,bt->bd", cast(enc), cast(w), precision=HI)
            x_t = pe_t + mm(context, p["decoder.w_in"][d_emb:])
            h = gru_step(x_t, h, p["decoder.gru.w_hzr"], p["decoder.gru.w_hc"], p["decoder.gru.b"])
            return h, h

        _, hs = lax.scan(step, h0, jnp.swapaxes(proj_emb, 0, 1))
        return jnp.swapaxes(hs, 0, 1)

    @jax.checkpoint
    def block_loss(hs, labels, out_w, out_b):
        logits = mm(hs, out_w) + out_b
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))

    def loss(p, batch):
        src = batch["source_ids"].astype(jnp.int32)
        trg = batch["target_ids"].astype(jnp.int32)
        labels = batch["label_ids"].astype(jnp.int32)
        emb_s = p["src_emb.w"][src]
        enc = jnp.concatenate(
            [gru(p, "enc.fw", emb_s, False), gru(p, "enc.bw", emb_s, True)], axis=-1
        )
        hs = decoder(p, enc, p["trg_emb_table"][trg])
        rows = hs.shape[0]
        blocks = 4 if rows % 4 == 0 else 1   # the [rows*T, V] logits in blocks
        total = 0.0
        for i in range(blocks):
            sl = slice(i * rows // blocks, (i + 1) * rows // blocks)
            total = total + block_loss(hs[sl], labels[sl], p["out_w"], p["out_b"])
        return total / rows

    return loss
