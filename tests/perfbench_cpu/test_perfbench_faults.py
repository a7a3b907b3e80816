"""`correct` has to come out false when the timed path is broken underneath,
once for each fault a cell can have, and when the reference computed in the
nearest lower precision (the control) takes the program's place. Tiny sizes;
the readings behind the cells' real limits are in PERF.md."""

import pytest

from perfbench_testlib import extended_base, extended_benchmark, run_cell


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


def test_a_token_altered_where_it_is_produced_is_not_correct(base, tmp_path, monkeypatch):
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
    r = run_cell(base, "servable_lm_tiny.chat_steady", seconds=1.5, tmp=tmp_path)
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


def test_serving_control_in_lower_precision_is_judged_not_correct(base):
    got = verdicts(build(base, "servable_lm_tiny.chat_steady"), window_s=1.5)
    assert got == {"program": True, "control:fp8": False, "fault:token_altered": False}


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
