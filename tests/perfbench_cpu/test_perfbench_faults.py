"""`correct` has to come out false when the timed path is broken underneath,
once for each fault a cell can have, and when the reference computed in the
nearest lower precision (the control) takes the program's place. Tiny sizes;
the readings behind the cells' real limits are in PERF.md."""

import pytest

from perfbench_testlib import SATURATED_CELL, SERVING_CELL, extended_base, extended_benchmark, run_cell


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return extended_base(tmp_path_factory.mktemp("pbdata"))


def break_step(monkeypatch, how):
    """Plant a fault in the program's train step (single and fused alike:
    make_multi_step scans the same step)."""
    from paddle_tpu.trainer.trainer import SGDTrainer

    build = SGDTrainer._build_step

    def broken(self):
        step = build(self)

        def bad(state, batch):
            if how == "state_unchanged":
                _, cost, extras = step(state, batch)
                return state, cost, extras
            rows = next(iter(batch.values())).shape[0]
            return step(state, {k: v[: rows // 2] for k, v in batch.items()})

        return bad

    monkeypatch.setattr(SGDTrainer, "_build_step", broken)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(base, tmp_path, monkeypatch, how):
    break_step(monkeypatch, how)
    r = run_cell(base, "mlp_tiny.train", tmp=tmp_path)
    assert r["correct"] is False
    over = [k for k, c in r["checks"].items() if not c["value"] <= c["limit"]]
    assert over and "window_compiles" not in over, r["checks"]


@pytest.mark.parametrize("cell", [SERVING_CELL, SATURATED_CELL])
def test_a_token_altered_where_it_is_produced_is_not_correct(base, tmp_path, monkeypatch, cell):
    from paddle_tpu.serving.session import ServingSession

    decode = ServingSession._decode_once
    calls = {"n": 0}

    def bad(self, *a, **kw):
        decode(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] % 3 == 0:   # alter the token every live request just got
            for _, act in self.scheduler.active_slots():
                if act.handle.tokens:
                    act.handle.tokens[-1] = (int(act.handle.tokens[-1]) + 7) % self.cfg.vocab

    monkeypatch.setattr(ServingSession, "_decode_once", bad)
    r = run_cell(base, cell, seconds=1.5, tmp=tmp_path)
    assert r["correct"] is False
    assert r["checks"]["token_logit_gap"]["value"] > r["checks"]["token_logit_gap"]["limit"]


def build(base, name, seed=3000000021):
    from perfbench import harness, registry

    cell = harness.load_cell(name, base=base, benchmark=extended_benchmark())
    return registry.load_module("builders", cell.config["builder"]).build(cell, seed)


def verdicts(system, **kw):
    """who -> the verdict of the run's own decide() on that reading."""
    from perfbench import harness

    return {r["who"]: harness.decide(system.judge(r["numbers"]))
            for r in system.calibrate(program=True, control=True, faults=True, **kw)}


def test_training_control_in_lower_precision_is_judged_not_correct(base):
    got = verdicts(build(base, "mlp_tiny.train"))
    assert got == {"program": True, "control:fp8": False, "fault:half_batch": False}


@pytest.mark.parametrize("cell", [SERVING_CELL, SATURATED_CELL])
def test_serving_control_in_lower_precision_is_judged_not_correct(base, cell):
    got = verdicts(build(base, cell), window_s=1.5)
    assert got == {"program": True, "control:bfloat16": False, "fault:token_altered": False}


def test_calibrate_prints_the_verdict_of_decide_beside_the_numbers(base, monkeypatch, capsys, tmp_path):
    import json

    from perfbench import calibrate, harness, registry

    monkeypatch.setattr(calibrate, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "load_cell", lambda name, load=harness.load_cell: load(
        name, base=base, benchmark=extended_benchmark()))
    assert calibrate.main(["--workload", "mlp_tiny.train", "--seeds", "5", "--control-seeds", "5",
                           "--fault-seeds", "5", "--allow-cpu"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert {r["who"]: r["correct"] for r in rows} == {
        "program": True, "control:fp8": False, "fault:half_batch": False}
    assert all(r["over"] for r in rows if not r["correct"]) and rows[0]["over"] == []
    limits = registry.load_workload("mlp_tiny.train", base)["check"]["limits"]
    assert set(rows[1]["over"]) <= set(limits)


def test_the_bfloat16_control_keeps_eight_bits_of_every_operand_and_passes_gradients_through():
    import jax
    import jax.numpy as jnp

    from perfbench.reference import lowprec

    cast = lowprec.CASTS["bfloat16"]
    x = jnp.asarray([1.0 + 2.0 ** -9, 1.0 + 2.0 ** -7, -3.0e5, 1e-3], jnp.float32)
    got = cast(x)
    assert got.dtype == jnp.float32
    assert got[0] == 1.0 and got[1] == 1.0 + 2.0 ** -7          # 8 bits of mantissa, no more
    assert jnp.allclose(got, x, rtol=2.0 ** -8) and not jnp.array_equal(got, x)
    assert jnp.array_equal(jax.grad(lambda v: cast(v).sum())(x), jnp.ones_like(x))


@pytest.mark.parametrize("limits,numbers,verdict", [
    ({"token_logit_gap": 0.1}, {"widest_gap": 0.05, "mean_gap": 9.0}, True),   # one limit: one number
    ({"token_logit_gap": 0.1, "token_logit_gap_mean": 1e-4}, {"widest_gap": 0.05, "mean_gap": 1e-6}, True),
    ({"token_logit_gap": 0.1, "token_logit_gap_mean": 1e-4}, {"widest_gap": 0.05, "mean_gap": 3e-3}, False),
    ({"token_logit_gap": 0.1, "token_logit_gap_mean": 1e-4}, {"widest_gap": 0.6, "mean_gap": 1e-6}, False),
    ({"token_logit_gap": 0.1, "token_logit_gap_mean": 1e-4}, {"widest_gap": 0.05}, False),  # a number missing: NaN fails
])
def test_a_served_cell_holds_each_of_its_limits_against_its_own_number(limits, numbers, verdict):
    from types import SimpleNamespace

    from perfbench import harness
    from perfbench.serving import ServeSystem

    cell = SimpleNamespace(config={}, workload={"check": {"limits": limits}}, chips=1)
    checks = ServeSystem(cell, 1).judge(numbers)
    assert set(checks) == set(limits) and all(checks[k][1] == v for k, v in limits.items())
    assert harness.decide(checks) is verdict


def test_the_configurations_matmul_precision_is_set_for_the_program_and_restored_at_release():
    """servable_lm_2048 states `high`; the tiny configuration states none, so
    that no test leaves jax's default changed for the tests after it."""
    from types import SimpleNamespace

    import jax

    from perfbench import registry
    from perfbench.serving import ServeSystem

    assert registry.load_config("servable_lm_2048")["matmul_precision"] == "high"
    before = jax.config.jax_default_matmul_precision
    system = ServeSystem(SimpleNamespace(config={}, workload={}, chips=1), 1)
    system._set_precision(None)                      # a configuration that states none: untouched
    assert jax.config.jax_default_matmul_precision == before
    try:
        system._set_precision("high")
        assert jax.config.jax_default_matmul_precision == "high"
    finally:
        system.release()
    assert jax.config.jax_default_matmul_precision == before
