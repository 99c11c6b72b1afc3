"""The Pallas kernels of the served path compile for a v5e chip.

Interpret mode (every other kernel test) does not check Mosaic's tiling
rules; these tests ask the TPU compiler itself, for a described — not
attached — v5e chip, at the TinyLlama-1.1B serving shapes: bf16, 32 query
and 4 kv heads of 64, 2048 pages, 8 sequences of up to 512 tokens, packed
chunks of 256 prefill lanes plus 8 decode riders.  Page size 16 is the
served one; 8 is ``ServingConfig``'s default.  The sampler compiles at
the benchmark's Qwen3 vocabulary (151 936) and 16 rows, and keeps its
argmax-only branch a real conditional: were the compiler to flatten it
into a select, a greedy batch would pay for the full-vocabulary sort.

Everything that touches the TPU library happens inside the fixtures below,
never at import: the library admits one process at a time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.packed_prefill import packed_prefill_attention
from repro.kernels.paged_attention import paged_attention

H, HKV, D = 32, 4, 64
POOL_PAGES, BATCH, MAX_SEQ, LANES = 2048, 8, 512, 256 + 8
VOCAB, SAMPLE_ROWS = 151936, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("page", [16, 8])
def test_paged_attention_compiles_for_v5e(one_chip, page):
    n_pages = MAX_SEQ // page
    pool = (POOL_PAGES, page, HKV, D)
    hlo = _compile(
        lambda q, k, v, bt, cl, occ: paged_attention(q, k, v, bt, cl,
                                                     occupancy=occ),
        one_chip, ((BATCH, H, D), jnp.bfloat16), (pool, jnp.bfloat16),
        (pool, jnp.bfloat16), ((BATCH, n_pages), jnp.int32),
        ((BATCH,), jnp.int32), ((BATCH,), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("page", [16, 8])
def test_packed_prefill_compiles_for_v5e(one_chip, page):
    n_pages = MAX_SEQ // page
    pool = (POOL_PAGES, page, HKV, D)
    hlo = _compile(
        packed_prefill_attention, one_chip,
        ((LANES, H, D), jnp.bfloat16), (pool, jnp.bfloat16),
        (pool, jnp.bfloat16), ((BATCH, n_pages), jnp.int32),
        ((LANES,), jnp.int32), ((LANES,), jnp.int32), ((BATCH,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_sampler_gate_compiles_for_v5e(one_chip):
    rows = (SAMPLE_ROWS,)
    hlo = _compile(
        ops.sample_tokens, one_chip, ((SAMPLE_ROWS, VOCAB), jnp.bfloat16),
        (rows, jnp.float32), (rows, jnp.int32), (rows, jnp.float32),
        (rows, jnp.int32), (rows, jnp.int32))
    assert " conditional(" in hlo
