"""The grouped expert product's Pallas kernel (ops/pallas/grouped_matmul.py)
in interpret mode against its oracle, `jax.lax.ragged_dot` over the whole
stack as serving/moe.py calls it: groups of 0, 1, a row tile less one, a
tile, a tile and one and many rows; rows past the groups, which come back
zero; layer 0 and layer 2 of a stack of three, the layer a constant and a
traced scalar; float32 and bfloat16 operands; a square product (Trinity's
first, K = 2F) and one that is not (granite's, K != N); several blocks of
columns; every row on one expert, and none on any."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gm

LAYERS = 3
# geometry: (K, N)
GEOMETRIES = {"trinity_like": (256, 256), "granite_like": (256, 384)}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")


def _tile(dtype):
    return gm._tiling(64, 128, 128, jnp.dtype(dtype).itemsize, jnp.dtype(dtype).itemsize)[0]


def _sizes(pattern, tile):
    return {
        # 0, 1, tile - 1, tile, tile + 1, 0 and many rows, starts that fall
        # inside a tile and on its edge
        "edges": [0, 1, tile - 1, tile, tile + 1, 0, 3 * tile + 5],
        "all_on_one": [0, 0, 5 * tile + 3, 0],
        "none": [0, 0, 0],
    }[pattern]


def _oracle(rows, w, sizes, layer):
    n_layers, e = w.shape[:2]
    groups = jnp.zeros(n_layers * e, jnp.int32).at[layer * e:(layer + 1) * e].set(sizes)
    with jax.default_matmul_precision("highest"):
        return jax.lax.ragged_dot(rows, w.reshape((n_layers * e,) + w.shape[2:]), groups,
                                  preferred_element_type=jnp.float32)


CASES = (
    [("edges", g, d, layer, traced) for g in GEOMETRIES for d in ("float32", "bfloat16")
     for layer in (0, 2) for traced in (False, True)]
    + [(p, g, d, 1, True) for p in ("all_on_one", "none") for g in GEOMETRIES
       for d in ("float32", "bfloat16")]
)


@pytest.mark.parametrize("pattern,geometry,dtype,layer,traced", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_the_kernel_is_ragged_dots_product(pattern, geometry, dtype, layer, traced):
    k, n = GEOMETRIES[geometry]
    sizes = np.asarray(_sizes(pattern, _tile(dtype)), np.int32)
    e, landed = len(sizes), int(sizes.sum())
    m = max(landed, 1) + 7                        # 7 rows past the groups: no token
    keys = jax.random.split(jax.random.PRNGKey(landed + layer), 2)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[1], (LAYERS, e, k, n), jnp.float32).astype(dtype)
    want = _oracle(rows, w, jnp.asarray(sizes), layer)
    if traced:
        fn = jax.jit(gm.grouped_matmul)
        args = (rows, w, jnp.asarray(sizes), jnp.asarray(layer, jnp.int32))
    else:
        fn = jax.jit(functools.partial(gm.grouped_matmul, layer=layer))
        args = (rows, w, jnp.asarray(sizes))
    with jax.default_matmul_precision("highest"):
        got = fn(*args)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got[landed:]), np.zeros((m - landed, n), np.float32))
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5 * scale), (
        float(jnp.max(jnp.abs(got - want))))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_several_blocks_of_columns_give_the_same_product(dtype):
    """Columns in blocks of 128 (the budget picks the whole width at these
    shapes): each block sees every expert again, an empty one fetching
    nothing."""
    tile = _tile(dtype)
    sizes = jnp.asarray([tile + 1, 0, 0, 2, tile - 1], jnp.int32)
    m, k, n = int(sizes.sum()) + 3, 128, 384
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[1], (LAYERS, 5, k, n), jnp.float32).astype(dtype)
    with jax.default_matmul_precision("highest"):
        got = gm._grouped_matmul(rows, w, sizes, jnp.asarray(1, jnp.int32), tile, 128)
    want = _oracle(rows, w, sizes, 1)
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                       atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_the_tiling_follows_the_shapes():
    """The row tile is one packed sublane tile of the rows' type; the
    columns' block is the widest that fits the budget with the rows, and
    none where the rows alone overflow it (ragged-dot's shapes then)."""
    assert gm._tiling(512, 2048, 2048, 2, 2) == (16, 2048)        # Trinity's decode step
    assert gm._tiling(640, 4096, 1536, 2, 2) == (16, 1536)        # granite's
    assert gm._tiling(64, 128, 128, 4, 4)[0] == 8
    tm, tn = gm._tiling(9088, 2048, 2048, 2, 2)
    assert tn % 128 == 0 and 2048 % tn == 0 and tn < 2048
    assert gm._tiling(16384, 2048, 2048, 2, 2) is None            # Trinity's 2048-token chunk
    assert not gm.fits(16384, 2048, 2048, jnp.bfloat16, jnp.bfloat16)
