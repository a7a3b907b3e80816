"""models.Seq2SeqModel under SGDTrainer (Adam, bf16 policy)."""

from perfbench.training import TrainSystem


def make_cost(cfg):
    from paddle_tpu import models

    return models.seq2seq(
        src_vocab=int(cfg["src_vocab"]), trg_vocab=int(cfg["trg_vocab"]),
        embed_dim=int(cfg["embed_dim"]), hidden_dim=int(cfg["hidden_dim"]),
    ).cost


def make_optimizer(opt):
    from paddle_tpu.optim import Adam

    return Adam(learning_rate=float(opt["lr"]))


def build(cell, seed):
    return TrainSystem(cell, seed, make_cost, make_optimizer)
