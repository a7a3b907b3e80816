"""Tensor-parallel serving on the named sharding-rules mesh (ISSUE 12).

Three contracts pinned here, all on the 8-device CPU host mesh:

  * RULES — parallel/rules.py is the ONE sharding vocabulary: logical axes
    resolve through the table for training (DataParallel.param_sharding)
    and serving (ServableLM) alike; legacy ParamAttr.sharding mesh-axis
    tuples translate through the same table (the deprecation shim); rank-
    mismatched specs are REJECTED naming the param (they used to be
    silently truncated — the data_parallel.py:54 bug).

  * TOKEN IDENTITY — TP=2 and TP=4 decode produce tokens bitwise identical
    to the single-chip oracle, greedy AND sampled (same per-request seeds),
    through whole-prompt and chunked prefill, with ONE decode signature
    (zero recompiles) for the whole lifetime. Attention is per-head
    independent, activations re-replicate at each row-parallel all-reduce,
    and sampling runs on replicated logits — so TP is result-invisible.

  * BYTES — per-chip param and KV-pool bytes shrink ~N× at TP=N, asserted
    from SHARDING METADATA (stats.per_chip_tree_bytes), not trust; and
    checkpoints are canonical full arrays, so one .npz loads bitwise onto
    any layout (single chip ↔ TP=2 ↔ TP=4, and a --shard_update training
    run's async-written checkpoint re-places onto a TP mesh bitwise
    through the updater's canonical seams)."""

import os

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from paddle_tpu.nn.graph import ParamAttr
from paddle_tpu.parallel import DataParallel, make_mesh
from paddle_tpu.parallel.rules import (
    DEFAULT_RULES,
    ShardingRules,
    make_tp_mesh,
)
from paddle_tpu.serving.model import ServableLM
from paddle_tpu.serving.session import ServingSession, make_demo_session
from paddle_tpu.serving.workload import (
    make_mixed_prompts,
    make_prompts,
    run_closed_loop,
)


# ---------------------------------------------------------------------------
# rules table
# ---------------------------------------------------------------------------


def test_default_rules_resolution():
    rules = ShardingRules()
    mesh = make_tp_mesh(2)
    assert rules.spec_for(("embed", "mlp"), mesh) == P(None, "model")
    assert rules.spec_for(("vocab", "embed"), mesh) == P("model", None)
    # batch -> data; the tp mesh HAS a data axis (size 1)
    assert rules.spec_for(("batch", "embed"), mesh) == P("data", None)
    # shorter specs pad with None (trailing dims replicated)
    assert rules.spec_for(("heads",), mesh, ndim=3) == P("model", None, None)


def test_rules_axis_absent_from_mesh_replicates():
    """The rules name the FULL vocabulary; a mesh without the target axis
    simply doesn't shard that entry — the same model runs on the data-only
    training mesh and the TP serving mesh without edits."""
    rules = ShardingRules()
    data_mesh = make_mesh({"data": 4})
    assert rules.spec_for(("embed", "mlp"), data_mesh) == P(None, None)
    assert rules.spec_for(("batch", "heads"), data_mesh) == P("data", None)


def test_rules_unknown_axis_raises_naming_param():
    with pytest.raises(KeyError, match=r"heds.*h\.w"):
        ShardingRules().spec_for(("embed", "heds"), make_tp_mesh(2), param="h.w")


def test_rules_pipeline_axis_reserved():
    """PARITY §2.5's reserved pipeline axis is a rules-table ENTRY now:
    present, unmapped — the day the mesh grows a pipe axis it is one edit."""
    assert "pipeline" in DEFAULT_RULES and DEFAULT_RULES["pipeline"] is None
    rules = ShardingRules().with_overrides(pipeline="model")
    assert rules.spec_for(("pipeline",), make_tp_mesh(2)) == P("model")


def test_legacy_mesh_axis_tuples_translate_through_table():
    """The deprecation shim: raw mesh-axis names in ParamAttr.sharding are
    their own logical names, resolved through the SAME table — old call
    sites (test_parallel, models/ctr.py) keep working unmodified."""
    mesh = make_mesh({"data": 4, "model": 2})
    dp = DataParallel(mesh, param_attrs={
        "w": ParamAttr(sharding=(None, "model")),
        "e": ParamAttr(logical_axes=("embed", "mlp")),
    })
    assert dp.param_sharding("w", 2).spec == P(None, "model")
    assert dp.param_sharding("e", 2).spec == P(None, "model")
    assert dp.param_sharding("unlisted", 2).spec == P()


def test_rank_mismatched_spec_rejected_naming_param():
    """Regression (ISSUE 12 satellite): param_sharding used to silently
    TRUNCATE a spec longer than the array's rank — a ("mlp", "embed") spec
    on a 1-D bias sharded the wrong dim without a word. Now it raises,
    naming the param."""
    dp = DataParallel(make_mesh({"data": 4, "model": 2}), param_attrs={
        "b": ParamAttr(sharding=("model", None)),
        "lb": ParamAttr(logical_axes=("mlp", "embed")),
    })
    with pytest.raises(ValueError, match="'b'"):
        dp.param_sharding("b", 1)
    with pytest.raises(ValueError, match="'lb'"):
        dp.param_sharding("lb", 1)
    # shorter-than-rank still pads (the documented convenience)
    assert dp.param_sharding("b", 3).spec == P("model", None, None)


# ---------------------------------------------------------------------------
# token identity + byte accounting
# ---------------------------------------------------------------------------

_DEMO = dict(vocab=64, n_layers=2, d_model=32, n_heads=4, seed=0,
             max_slots=4, page_size=8, max_new_limit=8)


def _greedy_run(tp):
    session = make_demo_session(prefill_buckets=(16, 32), tp=tp, **_DEMO)
    prompts = make_prompts(6, lengths=(5, 11, 16, 23), vocab=64, bos_id=1,
                           seed=0)
    res = run_closed_loop(session, prompts, 8, concurrency=4)
    return res.pop("results"), session.stats()


def _sampled_chunked_run(tp):
    """Sampling (temperature+top_k, per-request seeds) AND chunked prefill
    (long prompts beyond the bucket) in one leg — the two decode-path
    features PR 11 added must BOTH be TP-invariant."""
    session = make_demo_session(
        prefill_buckets=(16,), max_len=64, prefill_chunk=8, tp=tp,
        default_temperature=0.8, default_top_k=12, **_DEMO,
    )
    prompts = make_mixed_prompts(6, short_lengths=(5, 11), long_len=40,
                                 long_every=3, burst=1, vocab=64, bos_id=1,
                                 seed=1)
    res = run_closed_loop(session, prompts, 8, concurrency=4)
    return res.pop("results"), session.stats()


@pytest.fixture(scope="module")
def greedy_runs():
    return {tp: _greedy_run(tp) for tp in (0, 2, 4)}


@pytest.fixture(scope="module")
def sampled_runs():
    return {tp: _sampled_chunked_run(tp) for tp in (0, 2, 4)}


def test_tp_greedy_tokens_bitwise_identical(greedy_runs):
    tok0 = greedy_runs[0][0]
    assert greedy_runs[2][0] == tok0, "TP=2 greedy tokens diverged"
    assert greedy_runs[4][0] == tok0, "TP=4 greedy tokens diverged"
    assert all(t for t in tok0)  # every request actually produced tokens


def test_tp_sampled_chunked_tokens_bitwise_identical(sampled_runs):
    tok0 = sampled_runs[0][0]
    assert sampled_runs[2][0] == tok0, "TP=2 sampled/chunked tokens diverged"
    assert sampled_runs[4][0] == tok0, "TP=4 sampled/chunked tokens diverged"
    # the chunked path really ran (long prompts committed chunk-by-chunk)
    assert all(st["prefill_chunks_committed"] > 0
               for _, st in sampled_runs.values())


def test_tp_one_decode_signature(greedy_runs, sampled_runs):
    """The whole TP serving lifetime shares ONE compiled decode program —
    mesh-aware block tables ride as data, never shape."""
    for runs in (greedy_runs, sampled_runs):
        for tp, (_, st) in runs.items():
            assert st["decode_shape_signatures"] == 1, (tp, st)


def test_tp_step_in_flight_is_the_one_executable():
    """ISSUE 36 under TP: the token array a decode step returns is
    replicated over the mesh and feeds the next dispatch unfetched; the
    session's first dispatch (no step before it) runs the SAME executable,
    not a second lowering of it for a differently placed `prev_tok`."""
    session = make_demo_session(prefill_buckets=(16, 32), tp=2, **_DEMO)
    for p in make_prompts(3, lengths=(5, 11), vocab=64, bos_id=1, seed=0):
        session.submit(p, 8)
    session.run_until_idle()
    st = session.stats()
    assert st["overlapped_steps"] == st["decode_steps"] - 1 > 0
    assert st["decode_shape_signatures"] == 1
    assert session._decode._cache_size() == 1
    assert session._prev_tok.sharding.is_fully_replicated


def test_tp_param_and_pool_bytes_shrink(greedy_runs):
    """~N× per-chip shrink from sharding METADATA: the pool is fully
    kv_heads-sharded (exactly N×); params keep small replicated leaves
    (norms, biases, positions), so ≥ 0.6·N like shard_update_bench."""
    base = greedy_runs[0][1]
    for tp in (2, 4):
        st = greedy_runs[tp][1]
        assert st["tp"] == tp
        assert st["pool_bytes_per_chip"] * tp == base["pool_bytes_per_chip"]
        ratio = base["param_bytes_per_chip"] / st["param_bytes_per_chip"]
        assert ratio >= 0.6 * tp, (tp, ratio)


def test_tp_pool_reinit_keeps_sharding(greedy_runs):
    """Crash recovery re-creates the pools through the SAME cache seam: the
    re-init must land on the TP layout, or the first post-restart decode
    would silently reshard the whole pool every step."""
    session = make_demo_session(prefill_buckets=(16,), tp=2, **_DEMO)
    assert session.cache.pool_sharding is not None
    session.cache.reset()
    k2, v2 = session.cache.make_pools()
    assert k2.sharding.spec == P(None, None, None, "model")
    assert v2.sharding.spec == P(None, None, None, "model")


def test_tp_rejects_indivisible_heads():
    with pytest.raises(ValueError, match="n_heads"):
        make_demo_session(vocab=64, n_layers=1, d_model=32, n_heads=2,
                          seed=0, tp=4)


def test_tp_unknown_param_raises_not_replicates():
    """A param absent from param_logical_axes must raise under TP, not
    silently replicate — omission would quietly erode the per-chip memory
    win while every token-equality gate still passed."""
    from paddle_tpu.serving.model import LMConfig, ServableLM

    model = ServableLM(
        LMConfig(vocab=64, n_layers=1, d_model=32, n_heads=4, max_len=64),
        mesh=make_tp_mesh(2),
    )
    with pytest.raises(KeyError, match="mystery"):
        model.param_sharding("mystery", 2)
    # single-chip path stays permissive (no table lookup happens at all)
    single = ServableLM(
        LMConfig(vocab=64, n_layers=1, d_model=32, n_heads=4, max_len=64)
    )
    assert single.param_sharding("mystery", 2) is None


# ---------------------------------------------------------------------------
# cross-layout checkpoints
# ---------------------------------------------------------------------------


def test_servable_checkpoint_cross_layout_bitwise(tmp_path, greedy_runs):
    """One .npz, any layout: a checkpoint written FROM a TP=2 session
    (sharded params gather to canonical full arrays in save()) loads
    bitwise onto a single chip and onto TP=4, and the loaded TP=4 session
    decodes the oracle's exact tokens."""
    tp2 = make_demo_session(prefill_buckets=(16, 32), tp=2, **_DEMO)
    path = os.path.join(str(tmp_path), "tp2.npz")
    tp2.model.save(path, tp2.params)

    single_model, single_params = ServableLM.load(path)
    tp4_model, tp4_params = ServableLM.load(path, mesh=make_tp_mesh(4))
    for k in single_params:
        np.testing.assert_array_equal(
            np.asarray(single_params[k]).view(np.uint32),
            np.asarray(tp4_params[k]).view(np.uint32),
        )
    tp4 = ServingSession(
        tp4_model, tp4_params, max_slots=4, page_size=8,
        prefill_buckets=(16, 32), max_new_limit=8,
    )
    prompts = make_prompts(4, lengths=(5, 11, 16), vocab=64, bos_id=1, seed=0)
    got = run_closed_loop(tp4, prompts, 8, concurrency=4).pop("results")
    oracle = make_demo_session(prefill_buckets=(16, 32), tp=0, **_DEMO)
    want = run_closed_loop(oracle, prompts, 8, concurrency=4).pop("results")
    assert got == want


def test_shard_update_checkpoint_places_onto_tp_mesh_bitwise(tmp_path):
    """The training↔serving seam: a --shard_update run's ASYNC-written
    checkpoint (flat data-axis-sharded opt state gathered through
    to_canonical) holds canonical full params that re-place bitwise onto a
    dp×tp mesh through the rules table — one sharding vocabulary, both
    runtimes."""
    from paddle_tpu.nn import costs as C
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import SGDTrainer

    def build():
        reset_name_scope()
        x = L.Data("x", shape=(8,))
        lbl = L.Data("label", shape=())
        h = L.Fc(x, 16, act="relu", name="h")
        logits = L.Fc(h, 4, act=None, name="out")
        return C.ClassificationCost(logits, lbl, name="cost")

    rs = np.random.RandomState(0)
    x = rs.randn(32, 8).astype(np.float32)
    y = rs.randint(0, 4, 32)

    def reader():
        for i in range(0, 32, 16):
            yield {"x": x[i:i + 16], "label": y[i:i + 16]}

    # power-of-two lr: exact scale products keep sharded == replicated
    # bitwise on this XLA build (see tests/test_shard_update.py)
    dp = DataParallel(make_mesh({"data": 4}))
    tr = SGDTrainer(build(), SGD(learning_rate=0.125), parallel=dp, seed=3,
                    shard_update=True)
    tr.train(reader, num_passes=1, save_dir=str(tmp_path),
             async_checkpoint=True)
    tr.checkpoint_wait()

    with np.load(os.path.join(str(tmp_path), "pass-00000",
                              "params.npz")) as z:
        saved = {k: np.array(z[k]) for k in z.files}

    # replicated twin: same seed/data/optimizer, no sharded update — the
    # canonical checkpoint must be bitwise the same params
    dp2 = DataParallel(make_mesh({"data": 4}))
    tr2 = SGDTrainer(build(), SGD(learning_rate=0.125), parallel=dp2, seed=3,
                     shard_update=False)
    tr2.train(reader, num_passes=1)
    for k, v in tr2.state["params"].items():
        np.testing.assert_array_equal(
            saved[k].view(np.uint32), np.asarray(v).view(np.uint32)
        )

    # re-place the canonical arrays onto a dp×tp mesh through the rules
    # table (logical axes this time, not mesh tuples) and round-trip
    tp_dp = DataParallel(make_mesh({"data": 2, "model": 2}), param_attrs={
        "h.w": ParamAttr(logical_axes=("embed", "mlp")),
        "out.w": ParamAttr(logical_axes=("mlp", "embed")),
    })
    for k, v in saved.items():
        placed = jax.device_put(v, tp_dp.param_sharding(k, v.ndim))
        if k == "h.w":
            assert placed.sharding.spec == P(None, "model")
        np.testing.assert_array_equal(
            np.asarray(placed).view(np.uint32), v.view(np.uint32)
        )


# ---------------------------------------------------------------------------
# shared-prefix cache × TP (ISSUE 19)
# ---------------------------------------------------------------------------


def _prefix_run(tp, prefix):
    """Sampled + chunked + prefix-cache run: each prompt drains before the
    next submits, so later prompts genuinely alias the cached prefix."""
    session = make_demo_session(
        prefill_buckets=(16,), max_len=96, prefill_chunk=8, tp=tp,
        prefix_cache=prefix, **_DEMO,
    )
    sys_prompt = list(range(2, 26))  # 24 shared tokens = 3 pages of 8
    handles = []
    for i in range(4):
        handles.append(session.submit(
            sys_prompt + [30 + i, 31 + i], 6,
            seed=50 + i, temperature=0.6, top_k=12,
        ))
        session.run_until_idle()
    return [h.tokens for h in handles], session.stats()


def test_tp_prefix_cache_tokens_identical():
    """The prefix cache is HOST-side block-table state, so it composes with
    TP for free: aliased pages are just page ids in the replicated table,
    and the per-shard paged attention reads them like any other page. TP=2
    cache-on tokens must be bitwise the single-chip cache-off oracle, with
    a real hit rate and still ONE decode signature."""
    ref, _ = _prefix_run(0, False)
    for tp in (0, 2):
        out, st = _prefix_run(tp, True)
        assert out == ref, f"tp={tp} cache-on tokens diverged"
        assert st["prefix_hit_rate"] > 0.3, (tp, st["prefix_hit_rate"])
        assert st["prefix_pages_shared"] >= 9, (tp, st["prefix_pages_shared"])
        assert st["decode_shape_signatures"] == 1
