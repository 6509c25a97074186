"""The main-path Pallas kernels compile for a TPU v5e at Qwen3-14B widths.

Each test lowers and compiles one kernel for a described (not attached)
v5e chip and asserts the compiled program holds the kernel
(``tpu_custom_call``): what interpret mode cannot show — block shapes
Mosaic refuses to tile, scoped-VMEM overflows — fails here without a chip.
Widths: 40 query heads, 8 KV heads, head_dim 128, d_model 5120, d_ff 17408.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HQ, HKV, DH = 40, 8, 128
SCALE = 1.0 / math.sqrt(DH)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes, kernel=None):
    """Compile for the described chip; with ``kernel``, the custom call
    must carry that name (what the profiler's `XLA Ops` line shows)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call\(", text)
    return text


def test_flash_prefill_compiles(one_chip):
    from repro.kernels.attention.kernel import flash_attention
    qkv = ((HQ, 2048, DH), jnp.bfloat16)
    _compile(one_chip, lambda q, k, v: flash_attention(
        q, k, v, scale=SCALE, causal=True), qkv, qkv, qkv)


def test_cached_prefill_compiles(one_chip):
    """The serve step's prefill: 4 slots x 512 queries continuing an f32
    cache of 536 rows through the GQA wrapper."""
    from repro.kernels.attention.ops import mha_attention
    b, s, L = 4, 512, 536
    _compile(one_chip, lambda q, k, v, off, kl: mha_attention(
        q, k, v, q_offset=off, kv_len=kl, use_kernel=True),
        ((b, s, HQ, DH), jnp.float32), ((b, L, HKV, DH), jnp.float32),
        ((b, L, HKV, DH), jnp.float32), ((b,), jnp.int32), ((b,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_contiguous_decode_compiles(one_chip, dtype):
    from repro.kernels.attention.decode import gqa_decode_attention
    b, L = 8, 4096
    kv = ((b, L, HKV, DH), dtype)
    _compile(one_chip, lambda q, k, v, n: gqa_decode_attention(
        q, k, v, length=n), ((b, HQ, DH), dtype), kv, kv, ((b,), jnp.int32),
        kernel="decode_attention")


def test_contiguous_int8_decode_compiles(one_chip):
    from repro.kernels.attention.decode_int8 import \
        quantized_gqa_decode_attention
    b, L = 8, 4096
    kq, ks = ((b, L, HKV, DH), jnp.int8), ((b, L, HKV), jnp.float32)
    _compile(one_chip, lambda q, a, sa, v, sv, n:
             quantized_gqa_decode_attention(q, a, sa, v, sv, length=n),
             ((b, HQ, DH), jnp.bfloat16), kq, ks, kq, ks, ((b,), jnp.int32),
             kernel="quantized_decode_attention")


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_compiles(one_chip, quantized):
    """Page size 16, the `serve --page-size` default."""
    from repro.kernels.attention.decode import paged_gqa_decode_attention
    from repro.kernels.attention.decode_int8 import \
        paged_quantized_gqa_decode_attention
    b, page, max_pages = 8, 16, 256
    pool = (b * max_pages, page, HKV, DH)
    q = ((b, HQ, DH), jnp.float32)
    tables = (((b, max_pages), jnp.int32), ((b,), jnp.int32))
    if quantized:
        kq, ks = (pool, jnp.int8), (pool[:-1], jnp.float32)
        _compile(one_chip, lambda q, a, sa, v, sv, t, n:
                 paged_quantized_gqa_decode_attention(q, a, sa, v, sv, t,
                                                      length=n),
                 q, kq, ks, kq, ks, *tables,
                 kernel="paged_quantized_decode_attention")
    else:
        kv = (pool, jnp.float32)
        _compile(one_chip, lambda q, k, v, t, n: paged_gqa_decode_attention(
            q, k, v, t, length=n), q, kv, kv, *tables,
            kernel="paged_decode_attention")


@pytest.mark.parametrize("rank", [0, 1, 2])
def test_tuned_mlp_matmul_tile_compiles(one_chip, rank, tmp_path):
    """The tiles the tuner measures for the MLP up-projection (its top 3;
    rank 0 is what ``tune(..., measure_k=0)`` picks) fit the scoped VMEM
    the kernel asks the compiler for."""
    from repro.core.tiling import Tile
    from repro.kernels import autotune, registry
    from repro.kernels.matmul.ops import matmul
    m, k, n = 4096, 5120, 17408
    spec = registry.get("matmul")
    cands = spec.enumerate_candidates({"m": m, "n": n, "k": k},
                                      dtype_bytes=2, vmem_bytes=None, top=3)
    cands.sort(key=lambda c: (c.score, spec.tie_break(c.knobs)))
    if rank == 0:
        plan = autotune.tune("matmul", {"m": m, "n": n, "k": k},
                             jnp.bfloat16, measure_k=0,
                             cache=autotune.TuneCache(tmp_path / "t.json"))
        assert list(plan.knobs["tile"]) == list(cands[0].knobs["tile"])
    tile = Tile(*cands[rank].knobs["tile"])
    _compile(one_chip, lambda a, b: matmul(a, b, tile=tile, use_kernel=True),
             ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16))


@pytest.mark.parametrize("block_cols", [None, 512])
def test_spmv_compiles(one_chip, block_cols):
    """Whole-x and blocked ELL SpMV, 8-row blocks as `pack_csr` packs."""
    from repro.kernels.spmv.kernel import ell_spmv, ell_spmv_blocked
    rows, width, n = 4096, 128, 4096
    if block_cols is None:
        fn = lambda x, c, v: ell_spmv(x, c, v, block_rows=8)
    else:
        fn = lambda x, c, v: ell_spmv_blocked(x, c, v, block_rows=8,
                                              block_cols=block_cols)
    _compile(one_chip, fn, ((n,), jnp.float32), ((rows, width), jnp.int32),
             ((rows, width), jnp.float32))
