"""Compile every Pallas kernel of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jax, and
``get_topology_desc`` describes a ``v5e:2x2`` host that is not attached.
A compile that passes here is what the chip's compiler accepts (Mosaic
lowering, tiling alignment, VMEM limits); interpret-mode tests cannot
show that.  Each test asserts that the kernel survived as a
``tpu_custom_call`` in the compiled program.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

# one 131 072-element fused-solve chunk as (rows, 128) lanes
SOLVE_TILES = (1024, 128)
N_CLIENTS = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    from repro.compile_cache import compile_cache_off

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    with pytest.MonkeyPatch.context() as mp, compile_cache_off():
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _cnn_grad_width() -> int:
    from repro.models import cnn

    params = jax.eval_shape(cnn.init, jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("name", ["fused_solve_tiled", "selection_solve_tiled"])
def test_selection_kernels_compile(one_chip, name):
    from repro.kernels.selection_solve import kernel

    fn = functools.partial(getattr(kernel, name), s_bits=199_210 * 32.0,
                           tau=0.5, p_max=1.0)
    x = _f32(SOLVE_TILES, one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, x, x, x, x)


def _aggregate_shapes(one_chip):
    """128 clients x the CNN's flattened gradient, padded as ops.py pads."""
    from repro.kernels.masked_aggregate.kernel import CLIENT_BLK, LANE_BLK

    d = _cnn_grad_width()
    n = -(-N_CLIENTS // CLIENT_BLK) * CLIENT_BLK
    d = -(-d // LANE_BLK) * LANE_BLK
    return _f32((n, d), one_chip), _f32((n,), one_chip)


def test_masked_aggregate_compiles(one_chip):
    from repro.kernels.masked_aggregate.kernel import masked_aggregate_tiled

    g, coef = _aggregate_shapes(one_chip)
    assert "tpu_custom_call" in _compiled_text(masked_aggregate_tiled, g, coef)


def test_quantized_masked_aggregate_compiles(one_chip):
    from repro.kernels.masked_aggregate.kernel import (
        quantized_masked_aggregate_tiled)

    g, per_client = _aggregate_shapes(one_chip)
    text = _compiled_text(quantized_masked_aggregate_tiled, g, per_client, g,
                          per_client, per_client)
    assert "tpu_custom_call" in text


def test_kernel_expm1_matches_jnp_near_zero():
    """The kernels' ``expm1`` (Mosaic has none) keeps its relative
    accuracy near 0, where a plain ``exp(x) - 1`` cancels; the XLA paths
    use ``accurate_expm1``."""
    from repro.kernels.selection_solve.kernel import _expm1

    x = jnp.asarray(np.concatenate([[0.0], np.logspace(-7, 1.9, 64)]),
                    jnp.float32)
    got = np.asarray(jax.jit(_expm1)(x), np.float64)
    want = np.expm1(np.asarray(x, np.float64))
    np.testing.assert_allclose(got, want, rtol=4 * np.finfo(np.float32).eps,
                               atol=0)


@pytest.mark.parametrize("name,ref,lo,hi", [
    ("_exp", np.exp, -7.0, 1.92),
    ("_log2", np.log2, 0.0, 30.0),
])
def test_kernel_transcendentals_accurate(name, ref, lo, hi):
    """The kernels' own ``exp`` and ``log2`` stay within 2 f32 ulp over
    the closed forms' range (x <= 120 ln 2, 1 + P pg up to 1e30); the
    chip's built-in ones are off by tens to thousands of ulp."""
    from repro.kernels.selection_solve import kernel

    x = np.logspace(lo, hi, 4096)
    if name == "_exp":
        x = np.concatenate([[0.0], x])
    else:
        x = np.concatenate([[1.0, 1.0 + 2.0 ** -23, np.sqrt(2.0)], 1.0 + x])
    x = x.astype(np.float32)
    got = np.asarray(jax.jit(getattr(kernel, name))(x), np.float64)
    want = ref(x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=2 * np.finfo(np.float32).eps,
                               atol=0)


@pytest.mark.parametrize("fn,op", [
    ("element_p_min", "exponential_minus_one"),
    ("element_tx_time", "log"),
])
def test_closed_forms_request_highest_accuracy(fn, op):
    """The XLA closed forms ask for the most accurate transcendentals:
    the TPU's default f32 exp and log are too coarse for the fixed point
    (``repro.core.problem``)."""
    from repro.core import power

    x = jnp.ones(8, jnp.float32)
    f = functools.partial(getattr(power, fn), s_bits=1e6)
    if fn == "element_p_min":
        f = functools.partial(f, tau=0.1)
    text = jax.jit(f).lower(x, x, x).as_text()
    lines = [ln for ln in text.splitlines()
             if f"stablehlo.{op} " in ln and "result_accuracy" in ln]
    assert lines and all("HIGHEST" in ln for ln in lines)
