"""Capture a jax.profiler trace of the seq2seq NMT bench step (the second
north-star metric) and emit the HLO-category / source-line time tables.

Usage:  python benchmarks/profile_seq2seq.py [--batch 128] [--len 50]
Outputs: trace under --out (gitignored) + markdown tables on stdout.

Reference anchor: benchmark/paddle/rnn/rnn.py, benchmark/README.md:115-161.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from profile_resnet import fmt_tables, parse_xplane  # noqa: E402


def build_step(bs: int, seq_len: int, vocab: int, dim: int):
    import jax

    from paddle_tpu.core import dtypes
    from paddle_tpu.models import Seq2SeqModel
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGDTrainer

    dtypes.set_policy(dtypes.bf16_policy())
    reset_name_scope()
    model = Seq2SeqModel(vocab, vocab, embed_dim=dim, hidden_dim=dim)
    trainer = SGDTrainer(model.cost, Adam(learning_rate=1e-3))
    rs = np.random.RandomState(0)
    batch = {
        "source_ids": rs.randint(2, vocab, (bs, seq_len)).astype(np.int32),
        "source_ids.lengths": np.full(bs, seq_len, np.int32),
        "target_ids": rs.randint(2, vocab, (bs, seq_len)).astype(np.int32),
        "target_ids.lengths": np.full(bs, seq_len, np.int32),
        "label_ids": rs.randint(2, vocab, (bs, seq_len)).astype(np.int32),
        "label_ids.lengths": np.full(bs, seq_len, np.int32),
    }
    batch = jax.device_put(batch)
    trainer.init_state(batch)
    step = trainer._make_step()
    return trainer, step, batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--len", type=int, default=50, dest="seq_len")
    ap.add_argument("--vocab", type=int, default=30000)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--out", default="profiles/r04_s2s")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} platform={dev.platform}", flush=True)

    trainer, step, batch = build_step(args.batch, args.seq_len, args.vocab, args.dim)
    state = trainer.state

    t0 = time.perf_counter()
    state, cost, _ = step(state, batch)
    print(f"compile+first step: {time.perf_counter() - t0:.1f}s cost={float(cost):.3f}", flush=True)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, cost, _ = step(state, batch)
    float(cost)
    dt = (time.perf_counter() - t0) / args.steps
    toks = args.batch * args.seq_len / dt
    print(f"steady: {dt * 1000:.2f} ms/step  {toks:.0f} tokens/s", flush=True)

    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        for _ in range(3):
            state, cost, _ = step(state, batch)
        jax.block_until_ready(cost)
        float(cost)

    res, err = parse_xplane(args.out)
    if res is None:
        print("xplane parse failed:", err)
        return
    by_cat, by_src, n_steps = res
    print()
    print(fmt_tables(by_cat, by_src, n_steps, top=20))


if __name__ == "__main__":
    main()
