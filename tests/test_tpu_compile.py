"""Main-path Pallas kernels compiled for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed and compiles for a chip that
is described, not attached.  This catches what the interpret-mode tests in
``test_kernels.py`` cannot: block shapes the tiling refuses, lowering rules
(e.g. no unsigned reductions) and fast-memory limits.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never while a module is imported, and
every such compile lives in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fingerprint.kernel import fingerprint
from repro.kernels.flash_attention.kernel import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compile is written to the persistent cache but
        # cannot be read back without a chip: keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((122753, 2304), jnp.bfloat16),   # a large embedding-sized leaf
    ((33, 7), jnp.float32),           # ragged: pads inside one block
])
def test_fingerprint_compiles_for_v5e(one_chip, shape, dtype):
    leaf = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_hlo(fingerprint, leaf)


@pytest.mark.parametrize("hq,hkv,d", [(32, 8, 128), (12, 4, 64)])
def test_flash_compiles_for_v5e(one_chip, hq, hkv, d):
    S = 4096
    q = jax.ShapeDtypeStruct((1, hq, S, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, hkv, S, d), jnp.bfloat16,
                              sharding=one_chip)
    hlo = _compiled_hlo(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    assert "tpu_custom_call" in hlo
