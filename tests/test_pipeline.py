"""Device-prefetching input pipeline + recompile telemetry tests.

Covers DevicePrefetcher (ordering, device residency, sharding, worker-error
propagation, clean shutdown), the trainer's device-batch fast path, the
RecompileStats shape-signature counter, and the persistent compilation cache
wiring."""

import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import stats
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.data.pipeline import DevicePrefetcher, is_device_batch


def _raw_batches(n=6, bs=8, dim=4, classes=3, seed=0):
    rs = np.random.RandomState(seed)
    return [
        [(rs.randn(dim).astype(np.float32), int(i % classes)) for i in range(bs)]
        for _ in range(n)
    ]


def _feeder(dim=4, classes=3):
    return DataFeeder({"x": dense_vector(dim), "label": integer_value(classes)})


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------


def test_prefetcher_preserves_order_and_lands_on_device():
    import jax

    raws = _raw_batches()
    feeder = _feeder()
    sync = [feeder(r) for r in raws]
    got = list(DevicePrefetcher(lambda: iter(raws), feeder, prefetch_depth=2))
    assert len(got) == len(sync)
    for s, b in zip(sync, got):
        assert is_device_batch(b)
        assert all(isinstance(v, jax.Array) for v in b.values())
        np.testing.assert_array_equal(np.asarray(b["x"]), s["x"])
        np.testing.assert_array_equal(np.asarray(b["label"]), s["label"])


def test_prefetcher_accepts_dict_batches():
    """A reader already yielding feed-ready dicts (e.g. a DoubleBuffer)
    composes: the prefetcher only adds the device leg."""
    feeder = _feeder()
    dicts = [feeder(r) for r in _raw_batches(n=3)]
    got = list(DevicePrefetcher(lambda: iter(dicts), prefetch_depth=1))
    assert len(got) == 3 and all(is_device_batch(b) for b in got)


def test_prefetcher_propagates_worker_errors():
    def reader():
        yield _raw_batches(n=1)[0]
        raise RuntimeError("boom in feeder thread")

    with pytest.raises(RuntimeError, match="boom in feeder thread"):
        list(DevicePrefetcher(reader, _feeder(), prefetch_depth=1))


def test_prefetcher_clean_shutdown_on_early_exit():
    produced = []

    def reader():
        for i, r in enumerate(_raw_batches(n=100)):
            produced.append(i)
            yield r

    before = threading.active_count()
    it = iter(DevicePrefetcher(lambda: reader(), _feeder(), prefetch_depth=2))
    next(it)
    it.close()  # abandon mid-pass: the worker must retire, not spin
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before
    assert len(produced) < 100  # bounded queue stopped the producer early


def test_prefetcher_rejects_bad_depth_and_ragged_batches():
    with pytest.raises(ValueError, match="prefetch_depth"):
        DevicePrefetcher(lambda: iter(()), prefetch_depth=0)
    ragged = {"x": [np.zeros(2), np.zeros(3)]}
    # numpy >= 1.24 raises "inhomogeneous" itself; older paths hit _coerce's
    # object-dtype guard — either way the worker error reaches the consumer
    with pytest.raises(ValueError, match="ragged|inhomogeneous"):
        list(DevicePrefetcher(lambda: iter([ragged]), prefetch_depth=1))


def test_prefetcher_applies_parallel_sharding_and_pads_indivisible():
    import numpy as np

    from paddle_tpu.nn.graph import SAMPLE_MASK_KEY
    from paddle_tpu.parallel import DataParallel, make_mesh

    dp = DataParallel(make_mesh({"data": 8}))
    feeder = _feeder()
    good = feeder(_raw_batches(n=1, bs=16)[0])
    odd = feeder(_raw_batches(n=1, bs=9)[0])  # 9 % 8 != 0 → padded to 16
    got = list(
        DevicePrefetcher(lambda: iter([good, odd, good]), parallel=dp,
                         prefetch_depth=2)
    )
    assert len(got) == 3, "indivisible batch must pad+mask, not drop (ISSUE 5)"
    for b in got:
        assert is_device_batch(b)
        assert b["x"].sharding.is_equivalent_to(
            dp._batch_sharding, b["x"].ndim
        )
    padded = got[1]
    assert padded["x"].shape[0] == 16
    mask = np.asarray(padded[SAMPLE_MASK_KEY])
    assert mask.sum() == 9 and (mask[9:] == 0).all()


def test_trainer_reshards_device_batch_without_mesh_sharding():
    """A dict of device-resident arrays that never went through shard_batch
    must NOT take the fast path under DataParallel — the trainer reshards it
    onto the mesh instead of feeding default-device arrays to the step."""
    import jax

    from paddle_tpu.parallel import DataParallel, make_mesh

    dp = DataParallel(make_mesh({"data": 8}))
    feeder = _feeder()
    plain = {k: jax.device_put(v) for k, v in feeder(_raw_batches(n=1, bs=16)[0]).items()}
    assert is_device_batch(plain) and not dp.is_sharded_batch(plain)
    assert dp.is_sharded_batch(dp.shard_batch(plain))

    from paddle_tpu.trainer import EndPass

    trainer = _tiny_trainer()
    trainer.parallel = dp
    costs = []
    trainer.train(
        lambda: iter([plain, plain]), num_passes=1,
        event_handler=lambda e: costs.append(e.metrics["avg_cost"])
        if isinstance(e, EndPass)
        else None,
    )
    assert len(costs) == 1 and np.isfinite(costs[0])


def test_is_device_batch():
    import jax.numpy as jnp

    assert not is_device_batch({"x": np.zeros(3)})
    assert not is_device_batch({})
    assert not is_device_batch([np.zeros(3)])
    assert is_device_batch({"x": jnp.zeros(3)})


# ---------------------------------------------------------------------------
# trainer integration: device batches skip coerce/shard, telemetry flows
# ---------------------------------------------------------------------------


def _tiny_trainer():
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import SGDTrainer

    reset_name_scope()
    x = L.Data("x", shape=(4,))
    lbl = L.Data("label", shape=())
    logits = L.Fc(L.Fc(x, 16, act="relu"), 3, act=None)
    cost = C.ClassificationCost(logits, lbl)
    return SGDTrainer(cost, Adam(learning_rate=0.02), seed=1)


def test_trainer_trains_through_prefetcher():
    from paddle_tpu.trainer import EndPass

    raws = _raw_batches(n=8, bs=16)
    reader = DevicePrefetcher(lambda: iter(raws), _feeder(), prefetch_depth=3)
    trainer = _tiny_trainer()
    passes = []
    trainer.train(
        reader,
        num_passes=6,
        event_handler=lambda e: passes.append(e.metrics)
        if isinstance(e, EndPass)
        else None,
    )
    assert len(passes) == 6
    assert passes[-1]["avg_cost"] < passes[0]["avg_cost"]
    # one batch shape → one signature per pass, reported in EndPass metrics
    assert passes[-1]["shape_signatures"] == 1
    # test() takes the device-batch fast path too
    res = trainer.test(DevicePrefetcher(lambda: iter(raws), _feeder()))
    assert np.isfinite(res["cost"]) and res["samples"] == 8 * 16


def test_trainer_without_a_prefetcher_feeds_inside_its_input_wait():
    """No prefetcher: the reader's own work IS the wait, on the train
    thread, and no pipeline.* span is recorded."""
    from paddle_tpu.obs import trace

    trace.reset()
    trainer = _tiny_trainer()
    trainer.train(
        lambda: iter(_raw_batches(n=3, bs=16)), num_passes=1,
        feeder=_feeder(),
    )
    names = [r[0] for r in trace.TRACER.snapshot()]
    assert names.count("train.input_wait") == 4
    assert names.count("train.dispatch") == 3
    assert not [n for n in names if n.startswith("pipeline.")]


# ---------------------------------------------------------------------------
# RecompileStats
# ---------------------------------------------------------------------------


def test_batch_signature_keys_on_shape_dtype_not_values():
    a = stats.batch_signature({"x": np.zeros((4, 2), np.float32)})
    b = stats.batch_signature({"x": np.ones((4, 2), np.float32)})
    c = stats.batch_signature({"x": np.zeros((4, 3), np.float32)})
    d = stats.batch_signature({"x": np.zeros((4, 2), np.int32)})
    assert a == b and a != c and a != d


def test_recompile_stats_pass_reset_and_warning(caplog):
    rc = stats.RecompileStats(warn_threshold=3)
    sig = lambda n: stats.batch_signature({"x": np.zeros((n, 2))})  # noqa: E731
    rc.start_pass()
    assert rc.record(sig(1)) is True
    assert rc.record(sig(1)) is False  # seen this pass
    rc.record(sig(2))
    assert rc.pass_signatures() == 2
    with caplog.at_level("WARNING", logger="paddle_tpu.stats"):
        rc.record(sig(3))  # hits warn_threshold=3
    assert any("distinct batch shapes" in r.message for r in caplog.records)
    rc.start_pass()
    assert rc.pass_signatures() == 0
    assert rc.total_signatures() == 3
    assert "shape signatures" in rc.report()


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


def test_compilation_cache_round_trip():
    """The suite's own cache (conftest → enable_compilation_cache) misses on
    a new program, persists it, and serves a fresh jit of the same program
    from disk. The directory follows the one placement rule."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.init_ctx import (
        DEFAULT_CACHE_DIR,
        enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()
    assert cache_dir == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    )
    assert jax.config.jax_compilation_cache_dir == cache_dir
    # the directory outlives the run, so the program must be new EVERY run
    c = float(np.random.RandomState().rand())
    misses0 = stats.RECOMPILES.cache_misses
    f = jax.jit(lambda x: x * c + x[::-1])
    f(jnp.arange(193, dtype=jnp.float32)).block_until_ready()
    assert stats.RECOMPILES.cache_misses > misses0
    assert os.listdir(cache_dir)  # entries persisted
    # identical program from a fresh jit wrapper → served from the cache
    hits0 = stats.RECOMPILES.cache_hits
    g = jax.jit(lambda x: x * c + x[::-1])
    g(jnp.arange(193, dtype=jnp.float32)).block_until_ready()
    assert stats.RECOMPILES.cache_hits > hits0


def test_compilation_cache_dir_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set jax reads it itself: the program
    redirects nothing, and entries land there (fresh interpreter — jax reads
    the variable at import)."""
    import subprocess
    import sys

    outside = tmp_path / "outside_cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from paddle_tpu.core.init_ctx import enable_compilation_cache\n"
        "d = enable_compilation_cache()\n"
        "jax.jit(lambda x: x * 2.5 + 1)(jnp.ones(7)).block_until_ready()\n"
        "print(d)\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(outside),
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(outside)
    assert os.listdir(outside)
