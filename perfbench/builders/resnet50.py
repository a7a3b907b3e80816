"""models.resnet50 under SGDTrainer (momentum SGD, bf16 policy)."""

from perfbench.training import TrainSystem


def make_cost(cfg):
    from paddle_tpu import models

    return models.resnet50(
        num_classes=int(cfg["num_classes"]), image_size=int(cfg["image_size"])
    )[3]


def make_optimizer(opt):
    from paddle_tpu.optim import SGD

    return SGD(learning_rate=float(opt["lr"]), momentum=float(opt["momentum"]))


def build(cell, seed):
    return TrainSystem(cell, seed, make_cost, make_optimizer)
