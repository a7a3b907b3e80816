"""The routed expert block's grouped products, timed on the chip: the Pallas
kernel (ops/pallas/grouped_matmul.py) against XLA's ragged-dot as
serving/moe.py calls it (the whole stack reshaped [L * E, K, N], this
layer's groups the only ones with rows), at both served MoE geometries and
at several mean rows a held expert gets (T k / E held).

Each timing is a jitted loop of calls over the stack's layers in turn (the
layer a traced index, as a scanned decode step has it), the host's clock
around it after `block_until_ready`, the best of a few repeats divided by
the calls. Bytes are what the product must move at least: the weights of
every held expert that got a row, the rows in and the float32 rows out.
Routing is uniform over the routed experts (an absent expert's rows land
in no group). Every kernel timing is checked against ragged-dot's output
over the rows that landed.

Usage (chip only; one JSON line a timing on stdout and in --out):
  python benchmarks/grouped_matmul_bench.py [--out timings.jsonl]
      [--repeats 3] [--variants 1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from paddle_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

F32 = jnp.float32
PEAK_BYTES_S = 819e9  # TPU v5e HBM (Google Cloud, "TPU v5e")

# name: (layers, held experts, routed experts, top k, d_model, expert width)
GEOMETRIES = {
    "trinity_mini": (4, 128, 128, 8, 2048, 1024),
    "granite_4_0_h_small": (10, 36, 72, 10, 4096, 768),
}
RATIOS = (4, 8, 17.8, 36, 71, 128, 142)


def _sizes(rng, tokens, top_k, held, routed):
    picks = np.stack([rng.choice(routed, top_k, replace=False) for _ in range(tokens)])
    picks = picks[picks < held]                     # the first `held` are this chip's
    return np.bincount(picks, minlength=held).astype(np.int32)


def _timed(fn, args, calls, repeats):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best / calls, float(out)


def _cast(product):
    """The product handed on in the model's type, as serving/moe.py does
    (XLA:TPU refuses ragged-dot's float32 output consumed as it is at
    granite's 360 groups: a bitcast of a padded layout)."""
    return lambda *a: product(*a).astype(jnp.bfloat16)


def _looped(product, layers, calls):
    def run(rows, w, sizes):
        def body(i, acc):
            return acc + jnp.sum(_cast(product)(rows, w, sizes, i % layers).astype(F32))
        return jax.lax.fori_loop(0, calls, body, jnp.zeros((), F32))
    return jax.jit(run)


def ragged(rows, w, sizes, layer):
    n_layers, e = w.shape[:2]
    groups = jax.lax.dynamic_update_slice(jnp.zeros(n_layers * e, jnp.int32), sizes, (layer * e,))
    return jax.lax.ragged_dot(rows, w.reshape((n_layers * e,) + w.shape[2:]), groups,
                              preferred_element_type=F32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", help="also write the JSON lines to this file")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--variants", type=int, default=1,
                    help="also time other tilings at each geometry's decode shape")
    ap.add_argument("--geometry", action="append", choices=sorted(GEOMETRIES),
                    help="the geometries to time (default: all)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    lines = []

    def emit(**rec):
        rec["device_kind"] = dev.device_kind
        print(json.dumps(rec), flush=True)
        lines.append(rec)

    rng = np.random.default_rng(20261019)
    for geo in args.geometry or GEOMETRIES:
        layers, held, routed, top_k, d, f = GEOMETRIES[geo]
        key = jax.random.PRNGKey(len(geo))
        for prod, (k, n) in (("wi", (d, 2 * f)), ("wo", (f, d))):
            w = jax.random.normal(key, (layers, held, k, n), jnp.bfloat16)
            calls = 2 * layers
            for r in RATIOS:
                # rows a multiple of 128, as the served programs' are (XLA:TPU
                # refuses ragged-dot over 360 groups at 140 rows or 1300)
                m = max(128, int(round(r * held / 128)) * 128)
                tokens = m // top_k
                sizes = _sizes(rng, tokens, top_k, held, routed)
                rows = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.bfloat16)
                moved = (int(np.sum(sizes > 0)) * k * n * 2 + m * k * 2 + m * n * 4)
                base = dict(geometry=geo, product=prod, rows_per_expert=m / held, m=m, k=k, n=n,
                            landed=int(sizes.sum()), experts_with_rows=int(np.sum(sizes > 0)),
                            bytes=moved)
                sz, layer = jnp.asarray(sizes), jnp.asarray(1, jnp.int32)
                want = jax.jit(_cast(ragged))(rows, w, sz, layer).astype(F32)
                t_ref, _ = _timed(_looped(ragged, layers, calls), (rows, w, sz), calls, args.repeats)
                emit(impl="ragged_dot", ms=t_ref * 1e3, gb_s=moved / t_ref / 1e9,
                     peak_share=moved / t_ref / PEAK_BYTES_S, **base)
                tiling = gm._tiling(m, k, n, 2, 2)
                if tiling is None:
                    emit(impl="kernel", fits=False, **base)
                    continue
                variants = [tiling]
                if args.variants and r in RATIOS[:3:2]:
                    tm0, tn0 = tiling
                    variants += [(tm, tn0) for tm in (32, 64, 128)]
                    variants += [(tm0, tn) for tn in (512, 1024) if tn < tn0 and n % tn == 0]
                for tm, tn in variants:
                    def product(rows_, w_, sizes_, layer, tm=tm, tn=tn):
                        return gm._grouped_matmul(rows_, w_, sizes_, layer, tm, tn)
                    try:
                        got = jax.jit(_cast(product))(rows, w, sz, layer).astype(F32)
                        # the rows that landed: ragged-dot leaves the rows past the
                        # groups as it finds them on the chip, the kernel zeroes them
                        mine, ref = got[:base["landed"]], want[:base["landed"]]
                        err = float(jnp.max(jnp.abs(mine - ref)) / jnp.max(jnp.abs(ref)))
                        t, _ = _timed(_looped(product, layers, calls), (rows, w, sz), calls, args.repeats)
                    except Exception as e:  # a tiling Mosaic refuses: recorded, not fatal
                        emit(impl="kernel", tm=tm, tn=tn,
                             error=f"{type(e).__name__}: {str(e)[:200]}", **base)
                        continue
                    emit(impl="kernel", tm=tm, tn=tn, ms=t * 1e3,
                         gb_s=moved / t / 1e9, peak_share=moved / t / PEAK_BYTES_S,
                         speedup=t_ref / t, rel_err=err, **base)
                del rows, want
            del w
    if args.out:
        with open(args.out, "w") as fh:
            for rec in lines:
                fh.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
