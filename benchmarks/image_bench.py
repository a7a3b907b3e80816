"""Image-model training benchmark — reference benchmark/paddle/image parity
(alexnet.py / googlenet.py / vgg.py / smallnet_mnist_cifar.py; the
BASELINE.md ms/batch tables).

Usage:
  python benchmarks/image_bench.py --model alexnet --batch_sizes 64,128
  python benchmarks/image_bench.py --model resnet50 --image 224

Prints one JSON line per (model, batch) with ms/batch on the active backend.
"""

from __future__ import annotations

import argparse
import json
import time


def run_one(model_name: str, batch_size: int, image: int, steps: int, warmup: int):
    import jax
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.nn.graph import Network, reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer

    reset_name_scope()
    builders = {
        "alexnet": lambda: models.alexnet(image_size=image),
        "googlenet": lambda: models.googlenet(image_size=image),
        "vgg16": lambda: models.vgg16(image_size=image),
        "vgg19": lambda: models.vgg19(image_size=image),
        "resnet50": lambda: models.resnet50(image_size=image),
        "smallnet": lambda: models.lenet(),
    }
    img, label, logits, cost = builders[model_name]()
    trainer = SGDTrainer(cost, SGD(learning_rate=0.01, momentum=0.9))
    rs = np.random.RandomState(0)
    ishape = tuple(img.shape)
    batch = {
        img.name: rs.randn(batch_size, *ishape).astype(np.float32),
        label.name: rs.randint(0, 10, batch_size),
    }
    batch = jax.device_put(batch)  # keep H2D out of the timing
    trainer.init_state(batch)
    step = trainer._make_step()
    from paddle_tpu.core.benchmark import time_train_steps

    sec, _ = time_train_steps(step, trainer.state, batch, steps, warmup)
    ms = sec * 1e3
    print(json.dumps({
        "model": model_name, "batch_size": batch_size, "image": image,
        "ms_per_batch": round(ms, 3),
        "images_per_sec": round(batch_size / (ms / 1e3), 1),
        "backend": jax.default_backend(),
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet")
    ap.add_argument("--batch_sizes", default="64")
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()
    for bs in [int(b) for b in args.batch_sizes.split(",")]:
        run_one(args.model, bs, args.image, args.steps, args.warmup)


if __name__ == "__main__":
    main()
