"""Cross-cutting utils: layer-name crash context (CustomStackTrace parity),
flags."""

import os

import numpy as np
import pytest

import jax

from paddle_tpu.core.stack_trace import LayerError


def test_layer_error_names_failing_layer():
    from paddle_tpu.nn import layers as L
    from paddle_tpu.nn.graph import Network, reset_name_scope

    reset_name_scope()
    a = L.Data("a", shape=(4,))
    b = L.Data("b", shape=(5,))
    bad = L.Addto([a, b], name="mismatched_add")  # 4 vs 5: shape error inside
    net = Network([bad])
    with pytest.raises(LayerError) as ei:
        net.init(
            jax.random.PRNGKey(0),
            {"a": np.zeros((2, 4), np.float32), "b": np.zeros((2, 5), np.float32)},
        )
    assert "mismatched_add" in str(ei.value)
    assert ei.value.layer_name == "mismatched_add"


def test_chunk_evaluator_config_plumbing():
    """chunk_scheme/num_chunk_types/excluded flow config -> EvaluatorConfig ->
    constructed evaluator (VERDICT r2 missing #6)."""
    from paddle_tpu.config import parse_config
    from paddle_tpu.metrics.evaluators import ChunkEvaluator

    def cfg():
        from paddle_tpu.config import helpers as H
        from paddle_tpu.config.config_parser import outputs

        seq = H.data_layer(name="toks", size=9)
        lab = H.data_layer(name="tags", size=9)
        out = H.fc_layer(input=seq, size=9, act=H.SoftmaxActivation(), name="out")
        H.chunk_evaluator(input=out, label=lab, chunk_scheme="IOBES",
                          num_chunk_types=2, excluded_chunk_types=[1])
        outputs(H.classification_cost(input=out, label=lab, name="cost"))

    pc = parse_config(cfg, emit_proto=False)
    ecs = [e for e in pc.context.evaluators if e.type == "chunk"]
    assert ecs and ecs[0].chunk_scheme == "IOBES"
    assert ecs[0].num_chunk_types == 2
    assert ecs[0].excluded_chunk_types == [1]

    ev = ChunkEvaluator(scheme="IOBES", num_chunk_types=2,
                        excluded_chunk_types=[1])
    ev.start()
    # IOBES with 2 types: tags = type*4 + pos, O = 8.
    # seq: S(type0)=3, B-I-E(type1)=4,5,6 — type1 chunks are excluded.
    tags = np.array([[3, 4, 5, 6, 8]])
    ev.update(output=None if False else np.eye(9)[tags], label=tags,
              lengths=np.array([5]))
    assert ev.n_label == 1 and ev.n_pred == 1 and ev.correct == 1
    assert ev.finish() == 1.0


def test_value_printer_evaluator():
    from paddle_tpu.metrics.evaluators import ValuePrinter

    lines = []
    ev = ValuePrinter(writer=lines.append)
    ev.start()
    ev.update(output=np.ones((2, 3)))
    assert ev.finish() == 1.0
    assert lines and "value_printer" in lines[0] and "(2, 3)" in lines[0]


def test_seq_text_printer_rejects_missing_payload(tmp_path):
    """update() with neither output ids nor a usable beam payload must raise
    a clear ValueError, not TypeError on len(None)."""
    from paddle_tpu.metrics.evaluators import SequenceTextPrinter

    printer = SequenceTextPrinter(result_file=str(tmp_path / "out.txt"))
    printer.start()
    try:
        with pytest.raises(ValueError, match="neither"):
            printer.update()
        with pytest.raises(ValueError, match="neither"):
            printer.update(beam=None, output=None)
    finally:
        printer.finish()


# -- the chip is required where it was asked for -------------------------------


def test_init_use_tpu_raises_on_a_backend_nobody_asked_for():
    """use_tpu means the TPU: with jax's default backend 'cpu' and
    JAX_PLATFORMS not naming it, init() raises and names the platform it
    found; with the CPU asked for (this suite), the same call runs."""
    import jax

    from paddle_tpu.core import init_ctx

    asked = jax.config.jax_platforms
    assert "cpu" in asked
    init_ctx.init(use_tpu=True)
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
            init_ctx.init(use_tpu=True)
        init_ctx.init(use_tpu=False)
    finally:
        jax.config.update("jax_platforms", asked)
        init_ctx.init(use_tpu=True)


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke(*argv, **env):
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *argv],
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env), cwd=_REPO,
        capture_output=True, text=True, timeout=1500,
    )


def test_chip_smoke_is_not_a_pass_on_the_cpu():
    out = _chip_smoke()
    assert out.returncode != 0
    assert "platform: cpu" in out.stdout and "not a TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.slow
def test_chip_smoke_rehearsal_runs_every_phase():
    """The explicit CPU rehearsal drives all three phases at tiny sizes —
    on four virtual devices, so the four-chip legs (data mesh of 4, replica
    check, --tp=4) run too — and still is not a pass: exit code 3, no
    result line."""
    out = _chip_smoke(
        "--rehearse", XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    for phase in ("kernels", "serve", "train"):
        assert f"[{phase}] ok" in out.stdout
    assert "--tp=4: tokens equal" in out.stdout
    assert "bitwise equal on 4 chips" in out.stdout
    assert '"ok"' not in out.stdout
