"""Ahead-of-time compiles of the served kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for one device of a *described* v5e topology.  These tests catch what the
interpret-mode kernel tests cannot — a primitive Mosaic cannot lower, or a
tile that overflows the chip's scoped VMEM — at the widths the served
pre-filter scan runs (d up to 512 over a 2^20-row corpus).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import masked_l2_topk

N_ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described device's executables cannot be read back from a
        # persistent cache; keep any configured cache out of these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("d", [128, 256, 384, 512])
def test_masked_l2_topk_compiles_for_v5e(one_chip, d):
    q = jax.ShapeDtypeStruct((64, d), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((N_ROWS, d), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((N_ROWS,), jnp.bool_, sharding=one_chip)
    compiled = masked_l2_topk.lower(q, x, m, k=10, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
