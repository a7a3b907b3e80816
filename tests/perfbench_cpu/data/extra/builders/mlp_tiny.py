"""What a later PR's new configuration looks like: a builder of its own."""

from perfbench.training import TrainSystem


def make_cost(cfg):
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L

    x = L.Data("x", shape=(int(cfg["in_dim"]),))
    label = L.Data("label", shape=())
    h = L.Fc(x, int(cfg["hidden"]), act="tanh", name="h")
    logits = L.Fc(h, int(cfg["classes"]), act=None, name="logits")
    return C.ClassificationCost(logits, label, name="cost")


def make_optimizer(opt):
    from paddle_tpu.optim import SGD

    return SGD(learning_rate=float(opt["lr"]), momentum=float(opt["momentum"]))


def build(cell, seed):
    return TrainSystem(cell, seed, make_cost, make_optimizer)
