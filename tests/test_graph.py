"""Core graph-system tests: init/apply, param sharing, topo order, state updates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import costs as C
from paddle_tpu.nn import layers as L
from paddle_tpu.nn.graph import Argument, Network, ParamAttr, reset_name_scope


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_name_scope()


def test_fc_forward_shapes(rng):
    data = L.Data("x", shape=(16,))
    fc1 = L.Fc(data, size=32, act="relu")
    fc2 = L.Fc(fc1, size=4, act=None)
    net = Network(fc2)
    batch = {"x": np.random.RandomState(0).randn(8, 16).astype(np.float32)}
    params, states = net.init(rng, batch)
    outs, _ = net.apply(params, states, batch)
    assert outs[fc2.name].value.shape == (8, 4)
    # two weight matrices + two biases
    assert len(params) == 4


def test_param_sharing(rng):
    data = L.Data("x", shape=(8,))
    shared = ParamAttr(name="shared_w")
    a = L.Fc(data, size=8, act=None, bias=False, param_attr=shared)
    b = L.Fc(a, size=8, act=None, bias=False, param_attr=shared)
    net = Network(b)
    batch = {"x": np.zeros((2, 8), np.float32)}
    params, _ = net.init(rng, batch)
    assert list(params) == ["shared_w"]


def test_shared_param_shape_mismatch(rng):
    data = L.Data("x", shape=(8,))
    shared = ParamAttr(name="w")
    a = L.Fc(data, size=8, act=None, bias=False, param_attr=shared)
    b = L.Fc(a, size=4, act=None, bias=False, param_attr=shared)
    net = Network(b)
    # wrapped in LayerError carrying the failing layer's name
    # (CustomStackTrace parity)
    from paddle_tpu.core.stack_trace import LayerError

    with pytest.raises(LayerError, match="mismatch"):
        net.init(jax.random.PRNGKey(0), {"x": np.zeros((2, 8), np.float32)})


def test_batchnorm_state_updates(rng):
    data = L.Data("x", shape=(4,))
    bn = L.BatchNorm(data)
    net = Network(bn)
    x = np.random.RandomState(1).randn(32, 4).astype(np.float32) * 3 + 1
    params, states = net.init(rng, {"x": x}, train=True)
    outs, new_states = net.apply(params, states, {"x": x}, train=True)
    # train-mode output is normalized
    v = np.asarray(outs[bn.name].value)
    np.testing.assert_allclose(v.mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(v.std(0), 1.0, atol=1e-2)
    # moving stats moved toward batch stats
    mm = np.asarray(new_states[f"{bn.name}.moving_mean"])
    assert np.all(np.abs(mm) > 0)
    # eval mode uses moving stats and does not update state
    outs2, states2 = net.apply(params, new_states, {"x": x}, train=False)
    np.testing.assert_allclose(
        np.asarray(states2[f"{bn.name}.moving_mean"]), mm, rtol=1e-6
    )


def test_dropout_train_vs_eval(rng):
    data = L.Data("x", shape=(100,))
    drop = L.Dropout(data, rate=0.5)
    net = Network(drop)
    x = np.ones((4, 100), np.float32)
    params, states = net.init(rng, {"x": x})
    out_eval, _ = net.apply(params, states, {"x": x}, train=False)
    np.testing.assert_array_equal(np.asarray(out_eval[drop.name].value), x)
    out_train, _ = net.apply(
        params, states, {"x": x}, train=True, rng=jax.random.PRNGKey(3)
    )
    v = np.asarray(out_train[drop.name].value)
    assert ((v == 0) | (v == 2.0)).all()
    assert 0.3 < (v == 0).mean() < 0.7


def test_apply_is_jittable(rng):
    data = L.Data("x", shape=(16,))
    out = L.Fc(data, size=8, act="sigmoid")
    net = Network(out)
    batch = {"x": np.zeros((4, 16), np.float32)}
    params, states = net.init(rng, batch)

    @jax.jit
    def f(params, states, x):
        outs, _ = net.apply(params, states, {"x": x})
        return outs[out.name].value

    y = f(params, states, batch["x"])
    assert y.shape == (4, 8)


def test_topo_diamond(rng):
    data = L.Data("x", shape=(8,))
    a = L.Fc(data, size=8, act=None)
    b = L.Fc(data, size=8, act=None)
    c = L.Addto([a, b], act="relu")
    net = Network(c)
    names = [l.name for l in net.layer_order]
    assert names.index(data.name) < names.index(a.name)
    assert names.index(a.name) < names.index(c.name)
    assert len(names) == len(set(names))


def test_argument_seq_mask():
    v = jnp.zeros((2, 5, 3))
    arg = Argument(v, lengths=jnp.array([2, 5]))
    m = np.asarray(arg.mask())
    assert m.tolist() == [[1, 1, 0, 0, 0], [1, 1, 1, 1, 1]]


# -- a classification cost takes over its linear projection --------------------


def _xent_paths():
    from paddle_tpu.obs import metrics

    counter = metrics.REGISTRY.counter("paddle_tpu_fused_projection_xent_total")
    return {dict(s.labels)["path"]: s.value for s in counter.samples() if s.labels}


def _paths_traced(fn):
    """{'fused': n, 'unfused': m} the counter moved by while fn ran."""
    before = _xent_paths()
    fn()
    after = _xent_paths()
    return {k: after[k] - before.get(k, 0.0) for k in after if after[k] != before.get(k, 0.0)}


def _seq2seq_batch(vocab=40, bsz=3, t=4):
    rs = np.random.RandomState(0)
    ids = rs.randint(2, vocab, (bsz, t)).astype(np.int32)
    lens = np.array([4, 2, 3], np.int32)[:bsz]
    return {
        k: v for name in ("source_ids", "target_ids", "label_ids")
        for k, v in ((name, ids), (name + ".lengths", lens))
    }


def _head(second_consumer=False):
    x = L.Data("x", shape=(6,))
    lbl = L.Data("label", shape=())
    hid = L.Fc(x, 12, act="tanh", name="hid")
    logits = L.Fc(hid, 5, act=None, name="logits")
    cost = C.ClassificationCost(logits, lbl, name="cost")
    extra = L.Fc(logits, 3, act=None, name="after") if second_consumer else None
    return logits, cost, extra


def _head_batch(seed=3, n=8):
    rs = np.random.RandomState(seed)
    return {
        "x": rs.randn(n, 6).astype(np.float32),
        "label": rs.randint(0, 5, n).astype(np.int32),
    }


@pytest.mark.parametrize("model", ["seq2seq", "resnet50"])
def test_the_cost_takes_its_projection_in_both_benchmark_models(model):
    """Read from the counter's label, as a run's operator would: tracing
    the model's loss moves path="fused" by one, and "unfused" not at all."""
    from paddle_tpu import models

    if model == "seq2seq":
        m = models.seq2seq(40, 40, 8, 8)
        cost, logits, batch = m.cost, m.logits, _seq2seq_batch()
    else:
        _, _, logits, cost = models.resnet50(num_classes=10, image_size=32)
        batch = {
            "image": np.zeros((2, 32, 32, 3), np.float32),
            "label": np.zeros((2,), np.int32),
        }
    net = Network([cost])
    assert net.fused_projections == {cost.name: logits}
    shapes = {}

    def init():  # runs both layers as written, and is not counted
        shapes["p"], shapes["s"] = jax.eval_shape(
            lambda: net.init(jax.random.PRNGKey(0), batch)
        )

    def step():
        jax.eval_shape(
            lambda p, s: net.apply(p, s, batch, train=True)[0][cost.name].value,
            shapes["p"], shapes["s"],
        )

    assert _paths_traced(init) == {}
    assert _paths_traced(step) == {"fused": 1.0}


@pytest.mark.parametrize("wanted_by", ["extra_output", "second_layer", "softmax_input"])
def test_wanted_logits_keep_their_layer_and_train_to_the_same_numbers(wanted_by):
    """Where the projection's value is wanted elsewhere (an extra output, as
    an evaluator on the logits makes it; a second layer fed by it) or the
    cost does not read logits, both layers stay as they were: the counter
    says unfused, and loss and gradients equal the fused ones to 1e-6."""
    logits, cost, extra = _head(second_consumer=wanted_by == "second_layer")
    fused = Network([cost])
    assert fused.fused_projections == {"cost": logits}
    if wanted_by == "extra_output":
        plain = Network([cost, logits])
    elif wanted_by == "second_layer":
        plain = Network([cost, extra])
    else:
        probs = L.Fc(logits.inputs[0], 5, act="softmax", name="logits")
        plain = Network([C.ClassificationCost(probs, cost.inputs[1], name="cost", from_logits=False)])
    assert plain.fused_projections == {}
    batch = _head_batch()
    params, states = plain.init(jax.random.PRNGKey(0), batch)  # 'after.*' too

    def loss_and_grad(net):
        def f(p):
            outs, _ = net.apply(p, states, batch, train=True)
            return outs["cost"].value
        return jax.value_and_grad(f)(params)

    traced = {}
    traced["plain"] = _paths_traced(lambda: traced.update(p=loss_and_grad(plain)))
    traced["fused"] = _paths_traced(lambda: traced.update(f=loss_and_grad(fused)))
    assert traced["fused"] == {"fused": 1.0}
    # a cost on probabilities is no cost from logits: it is not counted
    assert traced["plain"] == ({} if wanted_by == "softmax_input" else {"unfused": 1.0})
    (lf, gf), (lp, gp) = traced["f"], traced["p"]
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-6, atol=1e-6)
    for k in gf:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gp[k]), rtol=1e-6, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("model", ["head", "seq2seq"])
def test_parameter_tree_is_the_same_fused_or_not(model):
    """Names in creation order, shapes, dtypes AND the initial values: init
    runs every layer as written whatever the steps will fuse, so each
    parameter is made at its own place in the rng stream."""
    if model == "head":
        logits, cost, _ = _head()
        batch = _head_batch()
    else:
        from paddle_tpu import models

        m = models.seq2seq(40, 40, 8, 8)
        logits, cost, batch = m.logits, m.cost, _seq2seq_batch()
    fused, plain = Network([cost]), Network([cost, logits])
    assert fused.fused_projections and not plain.fused_projections
    pf, sf = fused.init(jax.random.PRNGKey(4), batch)
    pp, sp = plain.init(jax.random.PRNGKey(4), batch)
    assert list(pf) == list(pp) and list(sf) == list(sp)
    for k in pf:
        assert pf[k].shape == pp[k].shape and pf[k].dtype == pp[k].dtype, k
        np.testing.assert_array_equal(np.asarray(pf[k]), np.asarray(pp[k]), err_msg=k)
    assert fused.param_attrs.keys() == plain.param_attrs.keys()


def test_a_checkpoint_written_before_the_fusion_loads_and_resumes():
    """tests/data/ckpt_pr26 was written by the parent of PR 27 (its commit
    1b5376e: one pass of four batches, momentum SGD, the head of _head())
    with the costs of the pass that followed, as that code computed them.
    Today's trainer, whose cost has taken the 'logits' layer's work, loads
    it under the same parameter names and carries on to the same costs."""
    import json
    import os

    from paddle_tpu.optim import SGD
    from paddle_tpu.trainer import EndIteration, SGDTrainer

    here = os.path.join(os.path.dirname(__file__), "data", "ckpt_pr26")
    with open(os.path.join(here, "expected.json")) as f:
        want = json.load(f)["next_pass_costs"]
    _, cost, _ = _head()
    tr = SGDTrainer(cost, SGD(learning_rate=0.1, momentum=0.9), seed=5)
    assert tr.network.fused_projections
    rs = np.random.RandomState(3)
    batches = [
        {"x": rs.randn(8, 6).astype(np.float32), "label": rs.randint(0, 5, 8).astype(np.int32)}
        for _ in range(4)
    ]
    tr.init_state(batches[0])
    tr.load(here, 0)
    got = []
    tr.train(
        lambda: iter(batches), num_passes=1,
        event_handler=lambda e: got.append(float(e.cost)) if isinstance(e, EndIteration) else None,
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _v1_linear_head(cost_helper):
    def config():
        from paddle_tpu.config import helpers as H

        x = H.data_layer(name="x", size=6)
        lbl = H.data_layer(name="label", size=5)
        out = H.fc_layer(input=x, size=5, act=H.LinearActivation(), name="out")
        return getattr(H, cost_helper)(input=out, label=lbl, name="cost")

    return config


@pytest.mark.parametrize(
    "topology", ["resnet50", "lenet", "v1_cross_entropy", "v1_classification_cost"]
)
def test_a_topology_whose_cost_took_its_projection_still_dumps(topology):
    """config/dump.py shapes every layer from init's values, the given-away
    Fc among them: the config is emitted, with that layer, its size and its
    parameters in it."""
    from paddle_tpu import models
    from paddle_tpu.config import dump
    from paddle_tpu.config.config_parser import parse_config

    if topology.startswith("v1_"):
        pc = parse_config(_v1_linear_head(topology[3:]))  # emit_proto=True
        mc, head, size = pc.model_config, "out", 5
        if topology == "v1_cross_entropy":  # no evaluator wants the logits
            assert Network(pc.outputs).fused_projections
    else:
        if topology == "resnet50":
            _, _, logits, cost = models.resnet50(num_classes=10, image_size=32)
        else:
            _, _, logits, cost = models.lenet()
        assert Network([cost]).fused_projections == {cost.name: logits}
        mc, head, size = dump.build_model_config(cost), logits.name, logits.size
        assert head in dump.dump_config(cost)
    lc = {l.name: l for l in mc.layers}[head]
    assert lc.size == size and lc.inputs[0].input_parameter_name
    assert lc.bias_parameter_name
