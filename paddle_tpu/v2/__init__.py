"""paddle_tpu.v2 — the user-facing v2-style API.

Parity surface: python/paddle/v2/__init__.py (layer, activation, pooling, attr,
data_type, networks, optimizer, trainer.SGD, event, reader, minibatch, dataset,
parameters, inference.infer, topology.Topology). The implementation beneath is
the TPU-native layer graph (paddle_tpu.nn) + compiled-step trainer — not SWIG
into a C++ GradientMachine — but user scripts written against the reference v2
API shape work unchanged.
"""

from __future__ import annotations

from paddle_tpu.v2 import activation as activation  # noqa: F401
from paddle_tpu.v2 import attr as attr  # noqa: F401
from paddle_tpu.v2 import data_type as data_type  # noqa: F401
from paddle_tpu.v2 import event as event  # noqa: F401
from paddle_tpu.v2 import inference as inference  # noqa: F401
from paddle_tpu.v2 import layer as layer  # noqa: F401
from paddle_tpu.v2 import networks as networks  # noqa: F401
from paddle_tpu.v2 import optimizer as optimizer  # noqa: F401
from paddle_tpu.v2 import parameters as parameters  # noqa: F401
from paddle_tpu.v2 import pooling as pooling  # noqa: F401
from paddle_tpu.v2 import topology as topology  # noqa: F401
from paddle_tpu.v2 import trainer as trainer  # noqa: F401
from paddle_tpu.v2 import plot as plot  # noqa: F401
from paddle_tpu.v2.inference import infer as infer  # noqa: F401
from paddle_tpu.v2.minibatch import batch as batch  # noqa: F401

from paddle_tpu.data import reader as reader  # noqa: F401
from paddle_tpu.data import datasets as dataset  # noqa: F401
from paddle_tpu.data import image as image  # noqa: F401


def init(use_gpu: bool = False, trainer_count: int = 1, seed: int = 0, **kwargs):
    """paddle.init analog (python/paddle/v2/__init__.py:65).

    `use_gpu` means "use the accelerator": True requires jax's default backend
    to be a TPU (core/init_ctx.require_tpu), False — the v2 scripts' default —
    runs on whatever backend jax picks. `trainer_count` maps to the
    data-parallel mesh size; it is recorded and consumed by trainer.SGD.
    """
    import paddle_tpu.core.init_ctx as ctx

    ctx.init(use_gpu=use_gpu, trainer_count=trainer_count, seed=seed, **kwargs)
