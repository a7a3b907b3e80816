"""The Mamba-2 decode kernel (ops/pallas/ssm_decode.py) against its oracle,
`mamba2.ssm_step` on the layer's slice as `HybridMoELM._ssm_decode` runs it
on the CPU, in interpret mode: the new state bit for bit, every other layer
and every inactive lane untouched, y to float32 rounding; and the tiny
hybrid model served through the kernel gives the oracle's tokens."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.obs import metrics
from paddle_tpu.ops.pallas import ssm_decode
from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM


def _counted(path):
    return metrics.REGISTRY.counter("paddle_tpu_ssm_decode_total").value(path=path)


# (slots, Mamba layers, heads, head dim P, state N): the tiny test config's
# (one tile of all four heads), chip_smoke's small model's (tiles of two
# heads, one block), and enough heads at granite's P and N for two blocks
SHAPES = {"tiny": (4, 3, 4, 16, 16), "one_block": (3, 2, 8, 64, 128),
          "two_blocks": (2, 2, 64, 64, 128)}


@pytest.mark.parametrize("lanes", ["all_active", "inactive_lanes", "dt_zero"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_oracle(monkeypatch, shape, lanes):
    s, m_layers, h, p, n = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(len(shape) * 7 + len(lanes)), 6)
    ssm = jax.random.normal(ks[0], (s, m_layers, h, p, n), jnp.float32)
    x = jax.random.normal(ks[1], (s, h, p)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (s, h)) - 2.0)
    a_neg = -jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(ks[4], (s, n)).astype(jnp.bfloat16)
    c = jax.random.normal(ks[5], (s, n)).astype(jnp.bfloat16)
    active = jnp.ones(s, bool)
    if lanes == "inactive_lanes":
        # a lane with no request: the decode step gives it dt = 0 and keeps
        # its state by the select, whatever the rest of its inputs hold
        active = active.at[1].set(False)
        dt = dt.at[1].set(0.0)
    elif lanes == "dt_zero":
        # active lanes whose step changes nothing: decay 1, input 0
        dt = dt.at[0].set(0.0).at[:, ::3].set(0.0)
    layer = m_layers - 1

    def run(flag):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        # a fresh function a flag (jit's cache is the function's), the layer
        # a traced scalar
        step = jax.jit(lambda *a: HybridMoELM._ssm_decode(*a))
        return step(ssm, jnp.asarray(layer, jnp.int32), x, dt, a_neg, b, c, active)

    before = _counted("kernel"), _counted("oracle")
    y_want, want = run("0")
    y_got, got = run("interpret")
    assert (_counted("kernel") - before[0], _counted("oracle") - before[1]) == (1, 1)
    got, want, old = np.asarray(got), np.asarray(want), np.asarray(ssm)
    assert got.shape == want.shape == old.shape
    assert (got == want).all(), int((got != want).sum())
    # only the traced layer of the active lanes moved
    assert (got[:, :layer] == old[:, :layer]).all()
    assert (got[~np.asarray(active)] == old[~np.asarray(active)]).all()
    if lanes == "dt_zero":
        zero = np.asarray(dt == 0.0)
        assert (got[:, layer][zero] == old[:, layer][zero]).all()
    assert y_got.shape == y_want.shape == (s, h, p) and y_got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(y_want)))
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want), rtol=0, atol=4e-7 * scale)


def test_the_tiling_comes_from_the_shapes():
    """Tiles of whole 128-lane rows where P divides 128, blocks of four
    buffers within the budget, one tile at least."""
    assert ssm_decode._tiling(128, 64, 128) == (2, 32)     # granite: 1 MiB blocks
    assert ssm_decode._tiling(8, 64, 128) == (2, 8)
    assert ssm_decode._tiling(4, 16, 16) == (4, 4)
    assert ssm_decode._tiling(6, 48, 128) == (2, 6)
    assert ssm_decode._tiling(2, 64, 4096) == (2, 2)       # over the budget: one tile


def test_the_tiny_model_served_through_the_kernel_gives_the_oracles_tokens(monkeypatch):
    """Three requests on two slots (one admitted into a slot another left,
    lanes idle between) through ServingSession: the kernel under the
    interpreter against the default CPU path, token for token; each decode
    program traced counts its two scanned Mamba runs."""
    from test_hybrid_moe_lm import PROMPT, session, tiny

    model, params = tiny()
    prompts = [PROMPT, [1, 9, 9, 200, 13], [1] + list(range(40, 60))]
    tokens = {}
    for flag in ("auto", "interpret"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        path = "kernel" if flag == "interpret" else "oracle"
        before = _counted(path)
        sess = session(model, params, max_slots=2)
        handles = [sess.submit(prompt, 7) for prompt in prompts]
        sess.run_until_idle()
        assert sess.decode_shape_signatures() == 1
        traced = _counted(path) - before
        assert traced > 0 and traced % 2 == 0, traced
        tokens[flag] = [[int(t) for t in h.tokens] for h in handles]
    assert tokens["auto"] == tokens["interpret"]
    assert all(len(t) == 7 for t in tokens["auto"])
