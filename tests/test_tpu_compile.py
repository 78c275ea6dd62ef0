"""Compile every serve-path Pallas kernel for a described TPU v5e chip.

Interpret mode (how the other kernel tests run on CPU) accepts block
layouts and VMEM footprints that the TPU compiler refuses.  These cases
compile the four serve kernels -- slab/paged decode and slab/paged chunk
stats, unquantized and int8 -- with ``interpret=False`` against a
``v5e:2x2`` topology described without a chip, at the attention widths of
two served models: OLMo-1B (16 KV heads, G=1) and the GQA model Yi-9B
(4 KV heads, G=8, so a 256-token chunk carries Q=2048 query rows per KV
head); both with d_head=128, k=64, ring b=128, 64-token pages, 256-token
prefill chunks and max_seq 2048.  Nothing runs; a refused layout or a VMEM
overflow raises.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill.swan_chunk import (
    swan_chunk_stats_paged_pallas, swan_chunk_stats_pallas)
from repro.kernels.swan_decode.swan_decode import (swan_decode_paged_pallas,
                                                   swan_decode_pallas)

# serve shapes with the default SwanConfig (k_max=64, b=128)
B, DH, S, K, RING, PAGE, CHUNK = 8, 128, 2048, 64, 128, 64, 256
N_PAGES = B * S // PAGE + 1            # every lane's pages + the trash page
# (KV heads, query heads per KV head) of each model's attention
WIDTHS = {"olmo-1b": (16, 1), "yi-9b": (4, 8)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _operands(kernel: str, quant: bool, width: str, sharding):
    KV, G = WIDTHS[width]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    vals = jnp.int8 if quant else jnp.bfloat16
    paged = kernel.endswith("paged")
    rows = (N_PAGES, KV, PAGE) if paged else (B, KV, S)
    packed = [sds(rows + (K,), vals), sds(rows + (K,), jnp.int8)] * 2
    scales = [sds(rows, jnp.float32)] * 2 if quant else []
    lanes = sds((B,), jnp.int32)
    table = [sds((B, S // PAGE), jnp.int32)] if paged else []
    if kernel.startswith("decode"):
        q = sds((B, KV, G, DH), jnp.bfloat16)
        ring = [sds((B, KV, RING, DH), jnp.bfloat16)] * 2 + [
            sds((B, RING), jnp.int32)]
        return [q, *packed, *ring, lanes, lanes, *table, *scales]
    q = sds((B, KV, CHUNK * G, DH), jnp.float32)
    return [q, *packed, lanes, *table, *scales]


KERNELS = {
    "decode": swan_decode_pallas,
    "decode_paged": swan_decode_paged_pallas,
    "chunk": swan_chunk_stats_pallas,
    "chunk_paged": swan_chunk_stats_paged_pallas,
}


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_serve_kernel_compiles_for_v5e(one_chip, kernel, quant, width):
    fn = KERNELS[kernel]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *_operands(kernel, quant, width, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
