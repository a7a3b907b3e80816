"""HLO collective-count lint for the data-parallel train step (ISSUE 5).

Compiles the trainer's step on a 4-device slice of the CPU host mesh and
counts the collective ops XLA emitted — the same way test_lint_hotloop.py
pins host syncs. A silent regression to chattier collectives (e.g. an
updater change that makes XLA emit per-parameter gathers where it combined
them, or an extra all-reduce from a stray unsharded reduction) changes these
counts and fails the build.

The counts are pinned for THIS model (3 Fc layers → 6 parameters) on the
CPU partitioner of the jax build in the container. On CPU the partitioner
realizes the sharded update's scatter leg as all-reduce + dynamic-slice
(the TPU weight-update-sharding pass forms a true reduce-scatter — PAPERS.md
"Automatic Cross-Replica Sharding of Weight Update..."), so the invariants
checked here are: the replicated path has NO gathers, the sharded path adds
a bounded number of all-gathers, and neither path's collective count scales
with batch or silently doubles."""

import re

import jax
import numpy as np
import pytest

from paddle_tpu.nn import costs as C
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.graph import reset_name_scope
from paddle_tpu.optim import SGD
from paddle_tpu.parallel import DataParallel, make_mesh
from paddle_tpu.trainer import SGDTrainer

COLLECTIVES = (
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    "all-to-all",
)


def _counts(txt):
    """Logical collectives per kind. XLA's combiner may fuse several
    all-reduces (or all-gathers) into ONE op over a tuple of operands —
    `%ar = (f32[64], f32[64,16], ...) all-reduce(%a, %b, ...)` — so an op
    counts once per operand: combined or not, seven gradients reduced are
    seven."""
    counts = dict.fromkeys(COLLECTIVES, 0)
    for op in COLLECTIVES:
        for m in re.finditer(rf" {op}(?:-start)?\(([^)]*)\)", txt):
            counts[op] += max(1, m.group(1).count("%"))
    return counts


def _built_trainer(shard, compression=None, extra_layer=False):
    reset_name_scope()
    x = L.Data("x", shape=(16,))
    lbl = L.Data("label", shape=())
    h = L.Fc(x, 64, act="relu", name="h")
    h2 = L.Fc(h, 32, act="relu", name="h2")
    if extra_layer:
        h2 = L.Fc(h2, 32, act="relu", name="h3")
    logits = L.Fc(h2, 4, act=None, name="out")
    cost = C.ClassificationCost(logits, lbl, name="cost")
    dp = DataParallel(make_mesh({"data": 4}))
    tr = SGDTrainer(
        cost, SGD(learning_rate=0.125), parallel=dp, seed=0,
        shard_update=shard, grad_compression=compression,
    )
    rs = np.random.RandomState(0)
    batch = dp.shard_batch({
        "x": rs.randn(32, 16).astype(np.float32),
        "label": rs.randint(0, 4, 32),
    })
    tr.init_state(batch)
    return tr, dp, batch


def _compiled_step_hlo(shard, compression=None, extra_layer=False):
    tr, _dp, batch = _built_trainer(shard, compression, extra_layer)
    # compile WITHOUT donation so the aliasing config cannot change op
    # counts between jax point releases; the collectives are identical
    return jax.jit(tr._build_step()).lower(tr.state, batch).compile().as_text()


def _compiled_multi_hlo(shard, k=4):
    """The K-step fused dispatch program (make_multi_step) for op pins."""
    tr, dp, batch = _built_trainer(shard)
    batches = dp.shard_batches(
        {key: np.stack([np.asarray(v)] * k) for key, v in batch.items()}
    )
    return tr.make_multi_step().lower(tr.state, batches).compile().as_text()


# measured on the container's CPU partitioner; a changed count means the
# step's collective structure changed — review and re-pin
PINNED = {
    "replicated": {"all-reduce": 7, "reduce-scatter": 0, "all-gather": 0,
                   "collective-permute": 0, "all-to-all": 0},
    # the sharded update concatenates its per-parameter payloads, and the
    # partitioner gathers the concatenation in ONE all-gather
    "sharded": {"all-reduce": 7, "reduce-scatter": 0, "all-gather": 1,
                "collective-permute": 0, "all-to-all": 0},
    "sharded_bf16": {"all-reduce": 7, "reduce-scatter": 0, "all-gather": 1,
                     "collective-permute": 0, "all-to-all": 0},
}


@pytest.mark.parametrize(
    "tag,shard,compression",
    [("replicated", False, None), ("sharded", True, None),
     ("sharded_bf16", True, "bf16")],
)
def test_collective_counts_pinned(tag, shard, compression):
    got = _counts(_compiled_step_hlo(shard, compression))
    assert got == PINNED[tag], (
        f"{tag} step now emits {got} (pinned {PINNED[tag]}) — the compiled "
        "train step's collective structure changed. If intentional (updater "
        "rework, XLA upgrade), re-pin after checking nothing regressed to "
        "per-parameter collectives; see tests/test_hlo_collectives.py"
    )


def test_replicated_path_has_no_gathers():
    """The replicated update must never gather/scatter params — its only
    collectives are gradient all-reduces (+ the cost mean)."""
    got = _counts(_compiled_step_hlo(False))
    assert got["all-gather"] == 0 and got["reduce-scatter"] == 0, got


def test_sharded_gathers_stay_bounded():
    """The sharded update concatenates per-param payloads, so its gather
    count must stay well under 2 collectives per parameter (6 params here;
    a per-param-per-leg regression would be >= 12)."""
    got = _counts(_compiled_step_hlo(True))
    n_params = 6
    assert 0 < got["all-gather"] <= n_params, got


# -- ZeRO-2/3 (ISSUE 14) -------------------------------------------------------
#
# zero2's contract is STRUCTURAL, not just a count: the K-dispatch program
# merges the window into one shard-local batch, so it compiles to a single
# fused forward/backward/update — NO while loop at all, and exactly the
# single-step collective budget regardless of K. zero1's K-dispatch keeps
# the scan: one while loop whose body repeats the per-step collectives K
# times (the op COUNT in the text stays small, but every op in the body
# executes per step — which is why the byte claim needs the loop gone, not
# just a low count).

WHILE_OP = re.compile(r" while\(")


def test_zero2_k_dispatch_one_scatter_per_dispatch():
    """Acceptance: zero2 at K emits exactly one grad reduce-scatter per
    DISPATCH (on the CPU partitioner the scatter realizes as the same
    all-reduce set as a single zero1 step — see module docstring), with no
    while loop to repeat it per step."""
    single = _counts(_compiled_step_hlo("zero1"))
    fused = _compiled_multi_hlo("zero2", k=4)
    assert not WHILE_OP.search(fused), (
        "the zero2 K-dispatch program contains a while loop — the window "
        "is being scanned per step instead of fused into one update"
    )
    assert _counts(fused) == single, (
        "zero2's fused dispatch must carry exactly the single-step "
        "collective budget (one scatter + one gather phase per DISPATCH)"
    )


def test_zero2_collectives_invariant_in_k():
    """The acceptance configuration (--steps_per_dispatch 16) compiles the
    same collective set as any other K — the scatter count is per-dispatch
    by construction, not per-step."""
    base = _counts(_compiled_multi_hlo("zero2", k=4))
    assert _counts(_compiled_multi_hlo("zero2", k=16)) == base
    assert _counts(_compiled_multi_hlo("zero2", k=8)) == base


def test_zero1_k_dispatch_keeps_per_step_collectives():
    """The contrast pin: zero1's K-dispatch is a scan — its collectives sit
    inside a while body and execute once per STEP."""
    assert WHILE_OP.search(_compiled_multi_hlo("zero1", k=4))


# zero3 step: 6 forward on-demand param all-gathers (one per flat param; the
# remat'd backward re-gathers CSE away on the CPU partitioner) and the same
# 7 all-reduces as the replicated/zero1 step — the grad scatter rides the
# baseline grad reductions (all-reduce + shard slice on CPU; a true
# reduce-scatter under the TPU weight-update-sharding pass), so sharding
# the PARAMS adds zero reduce ops. Measured on the container's
# CPU partitioner.
ZERO3_PINNED = {
    "all-reduce": 7, "reduce-scatter": 0, "all-gather": 6,
    "collective-permute": 0, "all-to-all": 0,
}


def test_zero3_collective_counts_pinned():
    got = _counts(_compiled_step_hlo("zero3"))
    assert got == ZERO3_PINNED, (
        f"zero3 step now emits {got} (pinned {ZERO3_PINNED}) — the on-demand "
        "gather structure changed. If intentional, re-pin after checking the "
        "gathers stayed per-param (not per-use) and no trailing param "
        "all-gather appeared; see Zero3Updater in parallel/updaters.py"
    )


def test_zero3_gathers_scale_per_layer_scatters_do_not():
    """+1 Fc layer = +2 on-demand gathers (its w and b) and +2 grad
    all-reduces — exactly what the REPLICATED step also adds for that layer
    (its grad reductions). The zero3 scatter therefore adds NOTHING on top
    of the baseline: layer-count-invariant scatter cost, per-layer gather
    count."""
    base = _counts(_compiled_step_hlo("zero3"))
    plus = _counts(_compiled_step_hlo("zero3", extra_layer=True))
    assert plus["all-gather"] == base["all-gather"] + 2
    rep_base = _counts(_compiled_step_hlo(False))
    rep_plus = _counts(_compiled_step_hlo(False, extra_layer=True))
    assert (plus["all-reduce"] - base["all-reduce"]
            == rep_plus["all-reduce"] - rep_base["all-reduce"]), (
        "zero3's reduce count must track the replicated baseline's exactly "
        "— extra reduces mean the update grew its own per-layer scatters"
    )


def test_zero3_int8_gather_crosses_payload_and_scales():
    """int8 zero3: each flat param's gather crosses as (int8 payload, f32
    block scales) — two collectives per param instead of one, visible as
    roughly doubled all-gather ops (the narrow payload is what crosses on
    TPU; the CPU partitioner may fold the dequantize first — the module
    docstring's realization caveat)."""
    got = _counts(_compiled_step_hlo("zero3", compression="int8"))
    base = _counts(_compiled_step_hlo("zero3"))
    assert got["all-gather"] >= 2 * base["all-gather"], (got, base)


# -- tensor-parallel serving decode (ISSUE 12) --------------------------------
#
# The TP decode step's collective budget is FIXED by construction: one
# all-reduce for the vocab-sharded embed gather, one all-reduce per
# row-parallel projection (wo and w2 — two per layer), and one all-gather
# replicating the logits at the unembed output so sampling (greedy argmax
# AND the gumbel branch) runs with ZERO collectives. A stray resharding
# boundary — an activation left sharded, a constraint dropped, a sampling
# op crossing the vocab shards — changes these counts and fails loudly.
# Compile-only (.lower().compile(), never executed), so the persistent-cache
# multi-device execution gotcha does not apply.

N_LAYERS_TP = 2


def _compiled_tp_decode_hlo(tp: int, max_slots: int = 4,
                            n_layers: int = N_LAYERS_TP) -> str:
    import jax.numpy as jnp
    import numpy as np_

    from paddle_tpu.parallel.rules import make_tp_mesh
    from paddle_tpu.serving.model import LMConfig, ServableLM

    mesh = make_tp_mesh(tp) if tp > 1 else None
    model = ServableLM(
        LMConfig(vocab=64, n_layers=n_layers, d_model=32, n_heads=4,
                 max_len=64),
        mesh=mesh,
    )
    params = model.shard_params(model.init_params(jax.random.PRNGKey(0)))
    shape = (n_layers, 9, 8, 32)
    if mesh is not None:
        k_pages = jax.jit(
            lambda: jnp.zeros(shape), out_shardings=model.pool_sharding()
        )()
    else:
        k_pages = jnp.zeros(shape)
    s = max_slots
    args = (
        params, k_pages, k_pages,
        np_.zeros(s, np_.int32), np_.zeros(s, np_.int32), np_.ones(s, bool),
        np_.zeros((s, 8), np_.int32), np_.zeros(s, np_.uint32),
        np_.zeros(s, np_.int32), np_.zeros(s, np_.float32),
        np_.zeros(s, np_.int32),
    )
    return jax.jit(model.decode_step).lower(*args).compile().as_text()


# 1 embed all-reduce + 2 row-parallel all-reduces per layer; 1 logits
# all-gather. Measured on the container's CPU partitioner.
TP_DECODE_PINNED = {
    "all-reduce": 1 + 2 * N_LAYERS_TP,
    "reduce-scatter": 0,
    "all-gather": 1,
    "collective-permute": 0,
    "all-to-all": 0,
}


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_decode_collective_counts_pinned(tp):
    got = _counts(_compiled_tp_decode_hlo(tp))
    assert got == TP_DECODE_PINNED, (
        f"TP={tp} decode step now emits {got} (pinned {TP_DECODE_PINNED}) — "
        "a resharding boundary moved. Expected: one embed all-reduce, one "
        "all-reduce per row-parallel projection (wo, w2), one logits "
        "all-gather, nothing in sampling; see serving/model.py _constrain "
        "sites before re-pinning"
    )


def test_tp_decode_collectives_do_not_scale_with_slots():
    """Slots are data, not shape — and not collectives either: doubling
    max_slots must not add a single collective op."""
    assert (_counts(_compiled_tp_decode_hlo(2, max_slots=8))
            == _counts(_compiled_tp_decode_hlo(2, max_slots=4)))


def test_tp_decode_collectives_scale_only_with_layers():
    """+1 layer = +2 all-reduces (its wo and w2), nothing else — the
    per-layer budget the ISSUE names, directly."""
    base = _counts(_compiled_tp_decode_hlo(2))
    plus = _counts(_compiled_tp_decode_hlo(2, n_layers=N_LAYERS_TP + 1))
    assert plus["all-reduce"] == base["all-reduce"] + 2
    assert plus["all-gather"] == base["all-gather"]


def test_tp_single_chip_decode_has_no_collectives():
    """tp=1 must compile the PR-11 single-chip program: zero collectives,
    zero partitioning artifacts — TP support is free when unused."""
    got = _counts(_compiled_tp_decode_hlo(1))
    assert all(v == 0 for v in got.values()), got


def test_tp_sampling_branch_is_collective_free():
    """The sampling math ALONE (greedy argmax + the gumbel/top-k branch) on
    replicated logits under the TP mesh: zero collectives — the all-gather
    pinned above belongs to the unembed output, not to sampling."""
    import numpy as np_

    from paddle_tpu.parallel.rules import make_tp_mesh
    from paddle_tpu.serving.model import LMConfig, ServableLM

    model = ServableLM(
        LMConfig(vocab=64, n_layers=1, d_model=32, n_heads=4, max_len=64),
        mesh=make_tp_mesh(2),
    )
    s = 4
    txt = jax.jit(model._sample).lower(
        np_.zeros((s, 64), np_.float32), np_.zeros(s, np_.uint32),
        np_.zeros(s, np_.int32), np_.ones(s, np_.float32),
        np_.full(s, 8, np_.int32),
    ).compile().as_text()
    got = _counts(txt)
    assert all(v == 0 for v in got.values()), got
