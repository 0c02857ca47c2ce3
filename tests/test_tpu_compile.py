"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode, which every other kernel test runs in, accepts block
shapes the TPU compiler refuses. Here each kernel is lowered and compiled
for a described (not attached) v5e chip at the widths the system runs:
Gemma-2B decode and chunked prefill in bf16 for the serving kernels,
RoBERTa-large q/v with a cohort of 4 at rank 8 for the aggregation
kernel. Nothing runs; a refusal raises here instead of on the chip.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# Gemma-2B serving widths (8 rows, 64-token prompts + 16 new tokens in
# 8-slot pages, 4 adapter slots at rank 8; MQA: 8 query heads, 1 KV head).
D, HEADS, HEAD_DIM, ROWS, SLOTS, R = 2048, 8, 256, 8, 4, 8
PAGE, PAGES_PER_ROW = 8, 10
POOL = ROWS * PAGES_PER_ROW + 1                     # + the trash page
CHUNK, SPEC_WINDOW = 16, 5
# RoBERTa-large aggregation widths
D_ROBERTA, COHORT = 1024, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back without one;
    keep these compiles out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d_out", [D, HEAD_DIM], ids=["q_o", "kv"])
def test_bgmv_compiles(one_chip, d_out):
    _compile(lambda x, a, b, i: ops.bgmv(x, a, b, i, interpret=False),
             one_chip, ((ROWS, D), BF16), ((SLOTS, D, R), F32),
             ((SLOTS, R, d_out), F32), ((ROWS,), I32))


def test_paged_attention_compiles(one_chip):
    _compile(lambda q, k, v, t, n: ops.paged_attention(
        q, k, v, t, n, page_size=PAGE, interpret=False), one_chip,
        ((ROWS, HEADS, HEAD_DIM), BF16), ((POOL, PAGE, 1, HEAD_DIM), BF16),
        ((POOL, PAGE, 1, HEAD_DIM), BF16), ((ROWS, PAGES_PER_ROW), I32),
        ((ROWS,), I32))


def test_paged_verify_attention_compiles(one_chip):
    _compile(lambda q, k, v, t, n, o: ops.paged_verify_attention(
        q, k, v, t, n, o, page_size=PAGE, interpret=False), one_chip,
        ((ROWS, SPEC_WINDOW, HEADS, HEAD_DIM), BF16),
        ((POOL, PAGE, 1, HEAD_DIM), BF16), ((POOL, PAGE, 1, HEAD_DIM), BF16),
        ((ROWS, PAGES_PER_ROW), I32), ((ROWS,), I32), ((ROWS,), I32))


def test_flash_attention_prefill_chunk_compiles(one_chip):
    """One prefill chunk against a row's whole page span, KV heads
    repeated to the query heads, at a traced offset — as the engine
    calls it."""
    skv = PAGES_PER_ROW * PAGE
    _compile(lambda q, k, v, off: ops.flash_attention(
        q, k, v, causal=True, q_offset=off, block_q=CHUNK, block_k=skv,
        interpret=False), one_chip,
        ((1, CHUNK, HEADS, HEAD_DIM), BF16), ((1, skv, HEADS, HEAD_DIM), BF16),
        ((1, skv, HEADS, HEAD_DIM), BF16), ((), I32))


@pytest.mark.parametrize("items", [None, 2 * 24], ids=["one", "vmapped"])
def test_recon_agg_compiles(one_chip, items):
    """Alone, and vmapped over q/v x 24 layers as the aggregation engine
    runs it (vmap adds a batch dim to every block, SMEM ones included)."""
    fn = lambda a, b, e: ops.recon_agg(a, b, e, interpret=False)  # noqa
    lead = ()
    if items is not None:
        fn, lead = jax.vmap(fn), (items,)
    _compile(fn, one_chip, (lead + (COHORT, D_ROBERTA, R), F32),
             (lead + (COHORT, R, D_ROBERTA), F32), (lead + (COHORT,), F32))
