"""LSTM text-classification benchmark — reference benchmark/paddle/rnn/rnn.py
parity (BASELINE.md LSTM rows: 2×lstm + fc, seq len 100, hidden
256/512/1280, bs 64/128/256).

Usage:
  python benchmarks/rnn_bench.py --hidden 256,512 --batch_sizes 64,128
"""

from __future__ import annotations

import argparse
import json
import time


def run_one(batch_size: int, hidden: int, seq_len: int, vocab: int,
            steps: int, warmup: int):
    import jax
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.nn.graph import Network, reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer

    reset_name_scope()
    ids, label, logits, cost = models.text_lstm(
        vocab_size=vocab, embed_dim=128, hidden_dim=hidden, num_layers=2
    )
    trainer = SGDTrainer(cost, SGD(learning_rate=0.01))
    rs = np.random.RandomState(0)
    batch = {
        ids.name: rs.randint(0, vocab, (batch_size, seq_len)).astype(np.int32),
        ids.name + ".lengths": np.full(batch_size, seq_len, np.int32),
        label.name: rs.randint(0, 2, batch_size),
    }
    batch = jax.device_put(batch)  # keep H2D out of the timing
    trainer.init_state(batch)
    step = trainer._make_step()
    from paddle_tpu.core.benchmark import time_train_steps

    sec, _ = time_train_steps(step, trainer.state, batch, steps, warmup)
    ms = sec * 1e3
    print(json.dumps({
        "model": "lstm_text_cls", "batch_size": batch_size, "hidden": hidden,
        "seq_len": seq_len, "ms_per_batch": round(ms, 3),
        "tokens_per_sec": round(batch_size * seq_len / (ms / 1e3), 0),
        "backend": jax.default_backend(),
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch_sizes", default="64")
    ap.add_argument("--hidden", default="256")
    ap.add_argument("--seq_len", type=int, default=100)
    ap.add_argument("--vocab", type=int, default=10000)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()
    for bs in [int(b) for b in args.batch_sizes.split(",")]:
        for h in [int(x) for x in args.hidden.split(",")]:
            run_one(bs, h, args.seq_len, args.vocab, args.steps, args.warmup)


if __name__ == "__main__":
    main()
