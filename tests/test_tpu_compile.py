"""Compile guards: the main path's kernels, compiled for a described v5e.

Nothing runs here. Each test lowers one Pallas kernel at internlm2-1.8b
widths and compiles it with the TPU's compiler for a chip that is
described, not attached, so a kernel the chip would refuse (an
unsupported cast, a block that does not tile, more VMEM than allowed)
fails on the CPU before any chip time is spent. The topology is built in
a module fixture, never at import: only one process may hold the TPU
library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.internlm2_1_8b import CONFIG
from repro.kernels import ops

D_MODEL, D_FF, VOCAB = CONFIG.d_model, CONFIG.d_ff, CONFIG.vocab_size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("k, n", [(D_MODEL, D_FF), (D_MODEL, VOCAB),
                                  (D_FF, D_MODEL)],
                         ids=["d_ff", "vocab", "down"])
@pytest.mark.parametrize("packed", [False, True], ids=["int8", "int4"])
def test_dequant_matmul_compiles_for_v5e(one_chip, packed, k, n):
    """(8, k) x (k, n): the MLP up/gate, LM-head and MLP down decode
    matmuls."""
    delta_rows = k // 2 if packed else k
    delta_dtype = jnp.uint8 if packed else jnp.int8
    kernel = ops.dequant_matmul_int4 if packed else ops.dequant_matmul

    def fn(x, base, delta):
        return kernel(x, base, 0.01, -3.0, delta, 1e-4, 7.0, interpret=False)

    compiled = _compile(
        fn,
        jax.ShapeDtypeStruct((8, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((delta_rows, n), delta_dtype, sharding=one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_quantized_l2_compiles_for_v5e(one_chip):
    """Index codes are uint8 (``HNSWIndex._codes``): q_proj's dim group,
    D = d_model**2, with a few vertices (the row block shrinks to 8)."""
    d, n = D_MODEL * D_MODEL, 4

    def fn(q, codes, scales, zps, mids):
        return ops.quantized_l2(q, codes, scales, zps, mids, interpret=False)

    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = _compile(
        fn,
        jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n, d), jnp.uint8, sharding=one_chip),
        vec, vec, vec,
    )
    assert "tpu_custom_call" in compiled.as_text()
