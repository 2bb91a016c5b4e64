"""The serving path's Pallas kernels compile for a TPU v5e chip at
llama3.2-1b widths.

The chip is described, not attached: the TPU compiler runs here and refuses
what the chip's compiler would (unaligned blocks, loads from HBM, too much
VMEM), which interpret mode never checks. Nothing runs, so results are
checked in tests/test_kernels.py. The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# llama3.2-1b: query heads, kv heads, head dim; the engine's page tokens
H, K, D, T = 32, 8, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_attention_compiles_for_v5e(one_chip):
    """Decode at serving shapes: 4 rows, a 512-page pool, 33 pages a row."""
    B, P, R = 4, 512, 33
    fn = functools.partial(ops.paged_attention, impl="pallas")
    text = _compiled_text(
        fn, one_chip,
        ((B, H, D), jnp.bfloat16),
        ((P, T, K, D), jnp.bfloat16),
        ((P, T, K, D), jnp.bfloat16),
        ((B, R), jnp.int32),
        ((B, R), jnp.int32),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq", [192, 1216])
def test_flash_attention_compiles_for_v5e(one_chip, seq):
    """Prefill of prompts padded to page multiples that 128 does not divide."""
    fn = functools.partial(ops.flash_attention, causal=True, impl="pallas")
    text = _compiled_text(
        fn, one_chip,
        ((1, seq, H, D), jnp.bfloat16),
        ((1, seq, K, D), jnp.bfloat16),
        ((1, seq, K, D), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text
