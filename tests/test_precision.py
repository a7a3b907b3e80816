"""ISSUE 9: mixed-precision training — bf16 compute with f32 masters — and
the remat/scan policies.

The contract under SGDTrainer(precision="bf16"):
  * dot/conv inputs cross to bfloat16 through Policy.cast (>= 1 bf16 dot in
    the compiled step's HLO), so the MXU runs its native path on TPU;
  * parameters are f32 MASTERS end to end — created f32, updated f32 by the
    optimizer, stored f32 by checkpoints — and NEVER round-trip through
    bf16 (pinned bitwise below with an off-bf16-grid master value);
  * numerically-sensitive reductions (xent, batch-norm statistics, the
    pass-cost average, the divergence guard's isfinite) stay f32;
  * a bf16-trained checkpoint resumes bitwise into an f32 trainer and vice
    versa (same f32 masters on disk), composing with shard_update /
    grad_compression / K-step dispatch / elastic resize;
  * remat ("dots" | "conv_only" | "full") changes step time and residual
    memory, never the applied updates.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import dtypes, preempt
from paddle_tpu.nn import costs as C
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.graph import reset_name_scope
from paddle_tpu.optim import SGD, Adam
from paddle_tpu.parallel import DataParallel, make_mesh
from paddle_tpu.trainer import SGDTrainer
from paddle_tpu.trainer.events import EndIteration, EndPass

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_name_scope()
    preempt.reset()


DIM, CLASSES = 16, 4


def _build_cost():
    x = L.Data("x", shape=(DIM,))
    lbl = L.Data("label", shape=())
    h = L.Fc(x, 24, act="relu", name="h")
    logits = L.Fc(h, CLASSES, act=None, name="out")
    return C.ClassificationCost(logits, lbl, name="cost")


def _data(n=96, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, DIM).astype(np.float32)
    y = (x.sum(-1) > 0).astype(np.int32) + 2 * (x[:, 0] > 0).astype(np.int32)
    return x, y


def _reader(x, y, bs=16):
    def reader():
        for i in range(0, len(x), bs):
            yield {"x": x[i:i + bs], "label": y[i:i + bs]}

    return reader


def _trainer(precision=None, remat=None, parallel=None, **kw):
    reset_name_scope()
    return SGDTrainer(
        _build_cost(),
        kw.pop("optimizer", SGD(learning_rate=0.125, momentum=0.5)),
        parallel=parallel, seed=5, precision=precision, remat=remat, **kw,
    )


def _batch(bs=16, seed=0):
    x, y = _data(bs, seed)
    return {"x": x, "label": y}


def _params(tr):
    return {k: np.asarray(v) for k, v in tr.state["params"].items()}


def _assert_bitwise(a, b, what=""):
    for k in a:
        assert np.array_equal(
            a[k].view(np.uint32), b[k].view(np.uint32)
        ), f"{what}: param {k} differs (max abs {np.abs(a[k] - b[k]).max()})"


# -- Policy / cast unit tests (tier-1 fast) -----------------------------------


def test_policy_get_spellings():
    assert dtypes.get("bf16") is dtypes.get("bfloat16")
    assert dtypes.get("f32") is dtypes.get("float32") is dtypes.get(None)
    with pytest.raises(ValueError, match="f32.*bf16"):
        dtypes.get("fp16")


def test_policy_names():
    assert dtypes.f32_policy().name == "f32"
    assert dtypes.bf16_policy().name == "bf16"


def test_policy_cast_floats_only():
    p = dtypes.bf16_policy()
    assert p.cast(jnp.ones((2,), jnp.float32)).dtype == jnp.bfloat16
    assert p.cast(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.bfloat16
    assert p.cast(jnp.ones((2,), jnp.int32)).dtype == jnp.int32
    assert p.cast(jnp.ones((2,), jnp.bool_)).dtype == jnp.bool_
    f = dtypes.f32_policy()
    assert f.cast(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32
    # old spelling stays callable (out-of-tree users)
    assert p.cast_compute(jnp.ones((2,), jnp.float32)).dtype == jnp.bfloat16


def test_trainer_precision_override_beats_ambient():
    tr = _trainer(precision="bf16")
    assert tr.precision == "bf16"
    with dtypes.policy_scope(dtypes.bf16_policy()):
        assert _trainer().precision == "bf16"  # ambient default
        assert _trainer(precision="f32").precision == "f32"  # explicit wins
    assert _trainer().precision == "f32"


def test_invalid_precision_and_remat_rejected():
    with pytest.raises(ValueError, match="f32"):
        _trainer(precision="fp8")
    with pytest.raises(ValueError, match="remat"):
        _trainer(remat="checkpoint_everything")
    tr = _trainer()
    with pytest.raises(ValueError, match="remat"):
        tr.train(_reader(*_data(16)), remat="bogus")


# -- HLO shape of the bf16 step ----------------------------------------------


def _step_hlo(tr, bs=16):
    batch = _batch(bs)
    tr.init_state(batch)
    return tr._make_step().lower(tr.state, batch).as_text()


def _bf16_dots(hlo):
    return [
        ln for ln in hlo.splitlines() if "dot_general" in ln and "bf16" in ln
    ]


def test_bf16_step_contains_bf16_dots():
    """The acceptance HLO assert: the bf16 step's dots run on bf16 inputs
    (forward AND the backward's grad dots), and the f32 step has none."""
    hlo = _step_hlo(_trainer(precision="bf16"))
    assert len(_bf16_dots(hlo)) >= 1, "no bf16 dot in the bf16 step"
    # every dot crossed the cast boundary: none left computing in f32
    f32_dots = [
        ln for ln in hlo.splitlines()
        if "dot_general" in ln and "bf16" not in ln
    ]
    assert not f32_dots, f32_dots
    assert not _bf16_dots(_step_hlo(_trainer(precision="f32")))


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry (scan
    and while bodies, cond branches, pjit and custom-vjp calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


# what may read a float32 value as large as the logits: work that the
# compiler fuses into the pass that reads the bf16 product, and no more
_FUSED_INTO_THE_READ = {
    "add", "sub", "mul", "div", "neg", "exp", "log", "max", "min", "eq", "ne",
    "select_n", "convert_element_type", "stop_gradient", "reduce_max",
    "reduce_sum", "argmax", "jit", "pjit", "custom_jvp_call", "custom_vjp_call",
}


def test_seq2seq_step_at_the_cell_sizes_has_no_float32_logits():
    """Shapes only, nothing runs: the loss and gradient of models.seq2seq
    (30000, 30000, 512, 512) at 512 x 50 under the bf16 policy, as
    seq2seq_nmt.train runs it. The fault this pins (ledger, PR 26: a third
    of that cell's step): Fc's bf16 product plus its float32 bias is
    float32; that float32 [512, 50, 30000] was the cross-entropy's residual,
    was reshaped to [25600, 30000] AFTER the projection and re-laid-out for
    it, and its gradient came back the same way. With the projection inside
    the cost's op: nothing of the logits' size is kept for the backward in
    any dtype; a float32 value that large is only ever read by elementwise
    work and reductions, which fuse into the pass over the bf16 product (so
    none is reshaped, transposed, sliced, multiplied by a matrix or carried
    by a loop); no 30000-wide operand that large is reshaped or transposed
    in any dtype; every product takes bf16 operands."""
    from paddle_tpu import models
    from paddle_tpu.nn.graph import Network

    bsz, t, vocab = 512, 50, 30000
    n = bsz * t
    policy = dtypes.bf16_policy()
    net = Network([models.seq2seq(vocab, vocab, 512, 512).cost])
    ids = jax.ShapeDtypeStruct((bsz, t), jnp.int32)
    lens = jax.ShapeDtypeStruct((bsz,), jnp.int32)
    batch = {
        k: v for name in ("source_ids", "target_ids", "label_ids")
        for k, v in ((name, ids), (name + ".lengths", lens))
    }
    tiny = {k: np.ones((2,) + v.shape[1:], np.int32) for k, v in batch.items()}
    params, states = jax.eval_shape(
        lambda: net.init(jax.random.PRNGKey(0), tiny, policy=policy)
    )

    def loss(p, st, b):
        outs, _ = net.apply(p, st, b, train=True, rng=jax.random.PRNGKey(1), policy=policy)
        return outs["cost"].value

    def logits_sized(aval):
        return hasattr(aval, "shape") and int(np.prod(aval.shape)) >= n * vocab

    # what the forward keeps for the backward: the leaves of the vjp function
    _, pullback = jax.eval_shape(lambda p, st, b: jax.vjp(lambda q: loss(q, st, b), p), params, states, batch)
    kept = [x for x in jax.tree_util.tree_leaves(pullback) if logits_sized(x)]
    assert not kept, kept

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, states, batch)
    relaid, misread, wide_dots, seen_f32 = [], [], [], 0
    for eqn in _all_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        big = [v.aval for v in eqn.invars if logits_sized(getattr(v, "aval", None))]
        if big and name in ("reshape", "transpose", "copy"):
            relaid.append((name, big[0].str_short()))
        big32 = [a for a in big if a.dtype == jnp.float32]
        seen_f32 += bool(big32)
        if big32 and name not in _FUSED_INTO_THE_READ:
            misread.append((name, big32[0].str_short()))
        if name == "dot_general":
            if {str(v.aval.dtype) for v in eqn.invars} != {"bfloat16"}:
                wide_dots.append([v.aval.str_short() for v in eqn.invars])
    assert not relaid, relaid[:5]
    assert not misread, misread[:5]
    assert not wide_dots, wide_dots[:5]
    assert seen_f32  # the walk did reach the op's float32 arithmetic


def test_policy_scope_reaches_rnn_attention_dots():
    """The seq2seq decoder's GRU/additive-attention matmuls take no policy
    parameter — they consult the AMBIENT dtypes.current() global.
    Network.init/apply pin the ambient to the trace's policy, so an explicit
    SGDTrainer(precision=...) wins over a contaminated process global in
    BOTH directions: the bench's f32 baseline leg stays all-f32 even though
    run_bench sets the ambient to bf16, and a bf16 trainer under an f32
    ambient gets bf16 dots in the recurrent core (the model the MFU push
    actually targets), not just in the Fc layers."""
    from paddle_tpu.models import Seq2SeqModel

    vocab, dim, bs, t = 50, 16, 4, 4
    rs = np.random.RandomState(0)
    s = rs.randint(2, vocab, (bs, t)).astype(np.int32)
    lens = np.full(bs, t, np.int32)
    batch = {
        "source_ids": s, "source_ids.lengths": lens,
        "target_ids": s, "target_ids.lengths": lens,
        "label_ids": s, "label_ids.lengths": lens,
    }

    def dots(precision, ambient):
        reset_name_scope()
        with dtypes.policy_scope(dtypes.get(ambient)):
            model = Seq2SeqModel(vocab, vocab, embed_dim=dim, hidden_dim=dim)
            tr = SGDTrainer(
                model.cost, SGD(learning_rate=0.1), seed=0,
                precision=precision,
            )
            tr.init_state(batch)
            hlo = tr._make_step().lower(tr.state, batch).as_text()
        lines = [ln for ln in hlo.splitlines() if "dot_general" in ln]
        return lines, [ln for ln in lines if "bf16" in ln]

    all_f32, bf16_in_f32 = dots("f32", ambient="bf16")
    assert all_f32 and not bf16_in_f32, bf16_in_f32[:3]
    all_bf16, bf16_in_bf16 = dots("bf16", ambient="f32")
    # every dot in the step — encoder/decoder GRU scans, attention scores
    # and context, projections, fwd AND bwd — crossed the cast boundary
    assert bf16_in_bf16 and len(bf16_in_bf16) == len(all_bf16), [
        ln for ln in all_bf16 if "bf16" not in ln
    ][:3]


def test_bf16_masters_stay_f32_in_state():
    tr = _trainer(precision="bf16")
    batch = _batch()
    tr.init_state(batch)
    step = tr._make_step()
    st, cost, _ = step(tr.state, batch)
    assert cost.dtype == jnp.float32  # pinned reduction
    for k, v in st["params"].items():
        assert v.dtype == jnp.float32, f"master {k} left f32"
    for k, slots in tr.updater.to_canonical(st["opt"])["slots"].items():
        for s in slots:
            assert s.dtype == jnp.float32, f"opt slot of {k} left f32"


def test_master_never_roundtrips_bf16():
    """The zero-round-trip half of the acceptance HLO assert, pinned
    behaviorally: an f32 master holding a value OFF the bf16 grid
    (1 + 2^-20) must survive a whole compiled step bitwise when the update
    is zero (lr_scale=0) — any f32→bf16→f32 round-trip of the master on the
    update path would flush the low mantissa bits."""
    off_grid = np.float32(1.0 + 2.0 ** -20)
    assert np.float32(jnp.asarray(off_grid, jnp.bfloat16)) != off_grid
    tr = _trainer(precision="bf16")
    batch = _batch()
    tr.init_state(batch)
    tr.state["params"] = {
        k: jnp.full_like(v, off_grid) for k, v in tr.state["params"].items()
    }
    tr.state["lr_scale"] = jnp.zeros((), jnp.float32)
    st, _, _ = tr._make_step()(tr.state, batch)
    for k, v in st["params"].items():
        got = np.asarray(v)
        assert (got == off_grid).all(), (
            f"master {k} lost low mantissa bits: {got.ravel()[0]!r} — a "
            "bf16 round-trip is on the master update path"
        )


def test_master_never_roundtrips_bf16_sharded_compressed():
    """Same pin through the ZeRO-1 sharded update with bf16-compressed
    collectives: the gather leg carries the parameter DELTA, so the f32
    master must survive even though both collective legs cross in bf16."""
    off_grid = np.float32(1.0 + 2.0 ** -20)
    dp = DataParallel(make_mesh({"data": 2}))
    tr = _trainer(
        precision="bf16", parallel=dp, shard_update=True,
        grad_compression="bf16",
    )
    x, y = _data(16)
    batch = {"x": x, "label": y}
    sharded = dp.shard_batch(batch)
    tr.init_state(sharded)
    state = dict(tr.state)
    state["params"] = {
        k: jnp.full_like(v, off_grid) for k, v in state["params"].items()
    }
    state["lr_scale"] = jnp.zeros((), jnp.float32)
    tr.state = dp.shard_state(state, opt_sharding=tr.updater.opt_leaf_sharding)
    st, _, _ = tr._make_step()(tr.state, sharded)
    for k, v in st["params"].items():
        assert (np.asarray(v) == off_grid).all(), k


# -- convergence smokes -------------------------------------------------------


def _run_passes(tr, passes=4, n=96, bs=16):
    x, y = _data(n)
    costs = []

    def handler(e):
        if isinstance(e, EndPass):
            costs.append(e.metrics["avg_cost"])

    tr.train(_reader(x, y, bs), num_passes=passes, event_handler=handler,
             log_period=10_000)
    return costs


def test_bf16_fc_convergence_tracks_f32():
    c32 = _run_passes(_trainer(precision="f32"))
    cbf = _run_passes(_trainer(precision="bf16"))
    assert cbf[-1] < cbf[0] * 0.9, cbf
    # same seed, same data: the bf16 loss curve tracks f32 to rounding
    np.testing.assert_allclose(cbf, c32, rtol=0.05, atol=5e-3)


@pytest.mark.slow
def test_bf16_lenet_convergence_smoke():
    """bf16 LeNet (conv path: Policy.cast inside ops/conv.py + batch-norm
    statistics pinned f32): cost drops like the f32 run at the same seed."""
    from paddle_tpu.models import lenet

    def run(precision):
        reset_name_scope()
        _img, _lbl, _logits, cost = lenet(num_classes=4)
        tr = SGDTrainer(
            cost, SGD(learning_rate=0.03125, momentum=0.5), seed=0,
            precision=precision,
        )
        rs = np.random.RandomState(1)
        n = 64
        x = rs.rand(n, 28, 28, 1).astype(np.float32)
        y = (x.mean(axis=(1, 2, 3)) * 4).astype(np.int32).clip(0, 3)
        costs = []

        def handler(e):
            if isinstance(e, EndPass):
                costs.append(e.metrics["avg_cost"])

        def reader():
            for i in range(0, n, 16):
                yield {"pixel": x[i:i + 16], "label": y[i:i + 16]}

        tr.train(reader, num_passes=6, event_handler=handler)
        return costs

    cbf = run("bf16")
    c32 = run("f32")
    assert cbf[-1] < cbf[0] * 0.9, cbf
    assert abs(cbf[-1] - c32[-1]) < 0.1 * max(c32[0] - c32[-1], 1e-3), (
        cbf, c32,
    )


@pytest.mark.slow
def test_bf16_seq2seq_convergence_smoke():
    """The NMT config of the MFU push: tiny seq2seq trains under bf16 with
    loss within tolerance of the f32 run at the same seed (attention-GRU
    decoder scan + fused xent, all through the policy seam)."""
    from paddle_tpu.models import Seq2SeqModel

    vocab, dim, bs, t = 50, 16, 8, 6
    rs = np.random.RandomState(0)
    src = rs.randint(2, vocab, (32, t)).astype(np.int32)
    # learnable rule: target mirrors source (copy task)
    batches = []
    for i in range(0, 32, bs):
        s = src[i:i + bs]
        batches.append({
            "source_ids": s,
            "source_ids.lengths": np.full(bs, t, np.int32),
            "target_ids": s,
            "target_ids.lengths": np.full(bs, t, np.int32),
            "label_ids": s,
            "label_ids.lengths": np.full(bs, t, np.int32),
        })

    def run(precision):
        reset_name_scope()
        model = Seq2SeqModel(vocab, vocab, embed_dim=dim, hidden_dim=dim)
        tr = SGDTrainer(
            model.cost, Adam(learning_rate=0.01), seed=0, precision=precision
        )
        costs = []

        def handler(e):
            if isinstance(e, EndPass):
                costs.append(e.metrics["avg_cost"])

        tr.train(lambda: iter(batches), num_passes=5, event_handler=handler,
                 log_period=10_000)
        return costs

    cbf = run("bf16")
    c32 = run("f32")
    assert cbf[-1] < cbf[0] * 0.8, cbf
    drop32 = c32[0] - c32[-1]
    assert abs(cbf[-1] - c32[-1]) < 0.15 * drop32, (cbf, c32)


# -- cross-precision checkpoints ----------------------------------------------


@pytest.mark.parametrize("save_prec,load_prec", [("bf16", "f32"), ("f32", "bf16")])
def test_cross_precision_checkpoint_masters_bitwise(
    tmp_path, save_prec, load_prec
):
    """Checkpoints store the f32 masters (and canonical f32 opt slots), so a
    bf16-trained checkpoint resumes BITWISE into an f32 trainer and vice
    versa — precision is a property of the step program, not the state."""
    tr1 = _trainer(precision=save_prec)
    x, y = _data(64)
    tr1.train(_reader(x, y), num_passes=2, save_dir=str(tmp_path))
    tr1.checkpoint_wait()

    tr2 = _trainer(precision=load_prec)
    tr2.init_state(_batch())
    tr2.load(str(tmp_path))
    _assert_bitwise(_params(tr1), _params(tr2),
                    f"{save_prec}->{load_prec} masters")
    c1 = tr1.updater.to_canonical(tr1.state["opt"])["slots"]
    c2 = tr2.updater.to_canonical(tr2.state["opt"])["slots"]
    for k, slots in c1.items():
        for a, b in zip(slots, c2[k]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), k
    # and the cross-precision resume actually trains on
    costs = _run_passes(tr2, passes=1)
    assert np.isfinite(costs).all()


def test_cross_precision_resume_continues_pass_count(tmp_path):
    """auto_resume across a precision switch: the f32 restart of a bf16 run
    skips the completed passes and continues from the stored masters."""
    tr1 = _trainer(precision="bf16")
    x, y = _data(64)
    tr1.train(_reader(x, y), num_passes=1, save_dir=str(tmp_path))
    tr1.checkpoint_wait()
    p_saved = _params(tr1)

    tr2 = _trainer(precision="f32")
    seen = []
    tr2.train(
        _reader(x, y), num_passes=2, save_dir=str(tmp_path), auto_resume=True,
        event_handler=lambda e: seen.append(e.pass_id)
        if isinstance(e, EndPass) else None,
    )
    assert seen == [1], seen  # pass 0 came from the bf16 checkpoint
    assert not np.array_equal(
        _params(tr2)["h.w"], p_saved["h.w"]
    ), "resumed pass applied no updates"


# -- composition: the acceptance-criteria flag stack --------------------------


def test_bf16_composes_shard_update_compression_kdispatch_resize(tmp_path):
    """--precision bf16 --shard_update --grad_compression bf16
    --steps_per_dispatch 16 --elastic (ISSUE 9 acceptance): convergence
    smoke through a live 2→4 resize, and the mid-flight checkpoint loads
    bitwise into an f32 trainer of the same stack."""
    dp = DataParallel(make_mesh({"data": 2}))
    tr = _trainer(
        precision="bf16", parallel=dp, shard_update=True,
        grad_compression="bf16",
    )
    x, y = _data(192, seed=3)
    costs = []
    resized = []

    def handler(e):
        if isinstance(e, EndIteration) and (e.pass_id, e.batch_id) == (0, 15):
            preempt.get().request_resize(4, reason="test resize")
        if isinstance(e, EndPass):
            costs.append(e.metrics["avg_cost"])
            resized.append(e.metrics.get("resize_epochs", 0))

    tr.train(
        _reader(x, y, bs=4), num_passes=3, event_handler=handler,
        steps_per_dispatch=16, save_dir=str(tmp_path), log_period=10_000,
    )
    tr.checkpoint_wait()
    assert sum(resized) == 1, resized  # the 2→4 epoch completed mid-pass
    assert tr.parallel.data_axis_size == 4
    assert np.isfinite(costs).all()
    assert costs[-1] < costs[0], costs  # still converging through it all

    # cross-precision load of the composed run's checkpoint: masters bitwise
    dp2 = DataParallel(make_mesh({"data": 4}))
    tr2 = _trainer(
        precision="f32", parallel=dp2, shard_update=True,
        grad_compression="bf16",
    )
    tr2.init_state(dp2.shard_batch({"x": x[:16], "label": y[:16]}))
    tr2.load(str(tmp_path))
    _assert_bitwise(_params(tr), _params(tr2), "bf16 composed -> f32")


# -- remat --------------------------------------------------------------------


@pytest.mark.parametrize("remat", ["dots", "conv_only", "full"])
def test_remat_never_changes_updates(remat):
    """Rematerialization replays the exact same ops in the backward pass:
    the trained parameters match the no-remat run (power-of-two lr keeps
    the comparison FMA-proof)."""
    base = _trainer()
    _run_passes(base, passes=2)
    rem = _trainer(remat=remat)
    _run_passes(rem, passes=2)
    p0, p1 = _params(base), _params(rem)
    for k in p0:
        np.testing.assert_allclose(
            p0[k], p1[k], rtol=1e-6, atol=1e-7, err_msg=f"{remat}: {k}"
        )


def test_train_remat_override_rebuilds_step():
    tr = _trainer()
    x, y = _data(32)
    tr.train(_reader(x, y), num_passes=1)
    fn_before = tr._step_fn
    tr.train(_reader(x, y), num_passes=1, remat="dots")
    assert tr.remat == "dots"
    assert tr._step_fn is not fn_before, "remat change must drop the program"
    tr.train(_reader(x, y), num_passes=1, remat="none")
    assert tr.remat is None


# -- nightly: the heavy precision-grid bench drill ----------------------------


@pytest.mark.nightly
@pytest.mark.timeout(420)
def test_nightly_precision_grid_drill():
    """Real-subprocess run of benchmarks/dispatch_bench.py: the precision ×
    remat grid leg parses, every entry carries a platform tag, and the
    before/after HLO cost buckets are present (ISSUE 9 satellite)."""
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "benchmarks", "dispatch_bench.py"),
            "--batches", "48", "--passes", "1", "--batch_size", "16",
            "--dim", "16", "--hidden", "16",
        ],
        capture_output=True, text=True, timeout=390,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    leg = data["precision_remat"]
    assert {(e["precision"], e["remat"]) for e in leg["grid"]} == {
        ("f32", "none"), ("f32", "dots"), ("bf16", "none"), ("bf16", "dots"),
    }
    for e in leg["grid"]:
        assert e["platform"], e
        assert e["steps_per_sec"] > 0, e
    for key in ("before_f32_none", "after_bf16_dots"):
        assert "top_buckets" in leg["hlo_cost"][key] or \
            "error" in leg["hlo_cost"][key], leg["hlo_cost"]
