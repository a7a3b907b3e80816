"""serving.ServableLM through ServingSession."""

from perfbench.serving import ServeSystem


def build(cell, seed):
    return ServeSystem(cell, seed)
