"""The main-path Pallas kernels compile for a described TPU v5e chip at
real widths — no chip attached, nothing runs.

Interpret-mode parity tests cannot see what the TPU compiler refuses
(block tiling, ops Mosaic does not lower); these compiles can.  The
topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.  Compiles run in the test's own process with the
persistent compilation cache off (an entry written for a described chip
cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_kernels(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("T", [1, 16])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_chunk_attention_phi4(one_chip, T, kv_dtype):
    """phi4-mini decode (T=1) and prefill-chunk (T=16) shapes: 24 query
    heads over 8 kv heads, head_dim 128, 16-token blocks."""
    from repro.kernels.paged_chunk_attention import paged_chunk_attention
    b, h, kvh, d, n_blocks, bs, nbmax = 8, 24, 8, 128, 2048, 16, 64
    S = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    args = [S((b, T, h, d), jnp.bfloat16),
            S((n_blocks, bs, kvh, d), kv_dtype),
            S((n_blocks, bs, kvh, d), kv_dtype),
            S((b, nbmax), jnp.int32), S((b, T), jnp.int32)]
    if kv_dtype == "int8":
        args += [S((n_blocks, bs), jnp.float32)] * 2

    def fn(*a):
        return paged_chunk_attention(*a, impl="kernel")
    assert _n_kernels(fn, *args) >= 1


@pytest.mark.parametrize("b,h,kvh,s,d", [
    (8, 16, 16, 1024, 64),      # gpt2-medium
    (2, 24, 8, 2048, 128),      # phi4-mini (GQA 24/8)
    (1, 24, 8, 64, 128),        # phi4-mini prefill: the rule's least blocks
    (1, 24, 8, 512, 128),       # phi4-mini prefill: the median prompt
])
def test_flash_attention_fwd_bwd(one_chip, b, h, kvh, s, d):
    from repro.kernels.flash_attention import flash_attention
    S = lambda shape: _sds(one_chip, shape, jnp.bfloat16)  # noqa: E731

    def loss(q, k, v):
        o = flash_attention(q, k, v, impl="kernel")
        return jnp.sum(o.astype(jnp.float32))
    # forward with residuals + the dKV and dQ kernels
    assert _n_kernels(jax.grad(loss, argnums=(0, 1, 2)),
                      S((b, h, s, d)), S((b, kvh, s, d)),
                      S((b, kvh, s, d))) >= 3


def test_rmsnorm_fwd_dx_dw(one_chip):
    """d_model 3072 (phi4-mini).  The public op refuses impl='kernel' off
    a TPU backend, so this drives its custom_vjp directly."""
    from repro.kernels.rmsnorm.ops import BLOCK_ROWS, _rmsnorm
    n, d = 4096, 3072

    def loss(x, w):
        y = _rmsnorm(x, w, 1e-6, False, BLOCK_ROWS)
        return jnp.sum(y.astype(jnp.float32))
    assert _n_kernels(jax.grad(loss, argnums=(0, 1)),
                      _sds(one_chip, (n, d), jnp.bfloat16),
                      _sds(one_chip, (d,), jnp.float32)) >= 3


def test_ssd_scan_fwd_bwd_zamba2(one_chip):
    """zamba2-1.2b mixer widths: 64 heads of 64, state 64, chunk 256."""
    from repro.kernels.ssd_scan.ops import _ssd_scan
    b, l, h, p, n, chunk = 1, 1024, 64, 64, 64, 256

    def loss(x, a, B, C):
        y, hfin = _ssd_scan(x, a, B, C, chunk, False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(hfin)
    S = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    assert _n_kernels(jax.grad(loss, argnums=(0, 1, 2, 3)),
                      S((b, l, h, p), jnp.bfloat16),
                      S((b, l, h), jnp.float32),
                      S((b, l, n), jnp.bfloat16),
                      S((b, l, n), jnp.bfloat16)) >= 2


@pytest.mark.parametrize("E,k", [(60, 4), (128, 8)])
def test_topk_gating_fwd_bwd(one_chip, E, k):
    """qwen2-moe (60 experts, top-4) and qwen3-moe (128, top-8) routers."""
    from repro.kernels.topk_gating.ops import _BLOCK_TOKENS, _topk_gating

    def loss(logits):
        w, _ = _topk_gating(logits, k, True, False, _BLOCK_TOKENS)
        return jnp.sum(w)
    assert _n_kernels(jax.grad(loss),
                      _sds(one_chip, (4096, E), jnp.float32)) >= 2
