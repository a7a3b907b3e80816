"""How tests/perfbench_cpu/data/tiny_tpu.xplane.pb was recorded (on the chip,
through the chip tool): a jitted loop of two matmuls with a host sleep
between calls, so the trace holds device ops with idle gaps between them.

    python3 tests/perfbench_cpu/data/record_tiny_trace.py chiprun_out/tiny_trace
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def two_matmuls(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    two_matmuls(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    jax.profiler.start_trace(tmp)
    for _ in range(5):
        two_matmuls(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir, "tiny_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print("recorded", os.path.getsize(os.path.join(out_dir, "tiny_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
