"""Compile-only checks against a described TPU v5e: the chip's compiler
accepts the main path's Pallas kernels at real widths and the engine's
float64 chunk program at its streaming chunk size.  Nothing runs; these
guard layouts and memory that interpret mode cannot see.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.power import engine_x64
from repro.core.sweep import _engine_program, _scenario_inputs, grid_spec
from repro.kernels.flash_attention import flash_attention
from repro.kernels.photonic_mac import photonic_mac
from repro.kernels.ssm_scan import ssm_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["photonic_mac", "flash_attention",
                                    "ssm_scan"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_cache):
    """Each kernel at the width the zamba2-1.2b path gives it:
    photonic_mac (2048 x 2048) . (2048 x 8192) with per-tile scales, the
    shared-attention prefill (1, 32, 1024, 64), and the mamba scan over
    64 heads of 1,024 steps with P = N = 64."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "photonic_mac":
        compiled = _compile(lambda x, w, sc: photonic_mac(x, w, sc),
                            s((2048, 2048), jnp.bfloat16),
                            s((2048, 8192), jnp.int8), s((16, 64)))
    elif kernel == "flash_attention":
        q = s((1, 32, 1024, 64))
        compiled = _compile(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=4096), q, q, q)
    else:
        x = s((64, 1024, 64))
        compiled = _compile(lambda x, a, b, c: ssm_scan(x, a, b, c),
                            x, s((64, 1024)), x, x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_engine_chunk_program_compiles_for_v5e(one_chip, no_cache):
    """The universal float64 chunk program at the streaming engine's
    65,536-row chunk, over the four topologies the Pareto searches use."""
    topologies = ("sprint", "spacx", "tree", "trine")
    spec = grid_spec(topologies)
    rows = 65536

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with engine_x64():
        cols = {k: s((rows,), jnp.float64) for k in spec.base}
        scen = {k: s(np.shape(v), v.dtype)
                for k, v in _scenario_inputs().items()}
        scalar = s((), jnp.float64)
        compiled = _engine_program(topologies, donate=True).lower(
            cols, s((rows,), jnp.int64), scen, scalar, scalar,
            scalar).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
