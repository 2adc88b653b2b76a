"""Gradient correctness for the fused flash-attention custom_vjp.

Pallas kernels run in interpret mode; the oracle is jax autodiff through
``attention_ref``.  Covers causal/non-causal, GQA group sizes > 1,
skv > sq (q_offset), and non-multiple-of-block sequence lengths, plus a
regression test that the forward's saved logsumexp residual matches the
reference softmax normalizer.  Cases with blocks ``None`` run at the
blocks ``ops.block_sizes`` picks, where dead causal blocks are skipped.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.interpret

RNG = np.random.default_rng(7)


def _rand(shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


GRAD_CASES = [
    # b, h, kvh, sq, skv, d, causal, blocks (None: the rule's)
    (1, 2, 2, 128, 128, 32, True, 64),      # MHA, causal, multi-block
    (1, 4, 2, 128, 128, 32, False, 64),     # GQA group 2, non-causal
    (2, 4, 1, 128, 192, 32, True, 64),      # GQA group 4, skv > sq (q_offset)
    (1, 2, 2, 160, 160, 32, True, 64),      # non-multiple-of-block seq
    (1, 2, 1, 100, 100, 32, False, 64),     # seq < block, unaligned
    (1, 2, 2, 1024, 1024, 32, True, None),  # 2x2 of 512: one dead block
    (1, 2, 1, 1024, 1278, 32, True, None),  # q_offset 254: 512 x 256 blocks
    (1, 1, 1, 1024, 1281, 32, True, None),  # q_offset 257: 512 x 128 blocks
    (1, 6, 2, 640, 640, 128, True, None),   # GQA group 3, d 128: 5x5 of 128
    (1, 2, 2, 1500, 1500, 32, True, None),  # unaligned: kv_len 1500 of 1536
    (1, 2, 2, 200, 1500, 32, False, None),  # cross-attention at 1500
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_attention_grads_match_ref(case):
    from repro.kernels.flash_attention import attention_ref, flash_attention
    b, h, kvh, sq, skv, d, causal, blocks = case
    q = _rand((b, h, sq, d))
    k = _rand((b, kvh, skv, d))
    v = _rand((b, kvh, skv, d))
    ct = _rand((b, h, sq, d))     # fixed cotangent exercises all rows

    def loss_kernel(q, k, v):
        out = flash_attention(q, k, v, causal=causal, impl="interpret",
                              bq=blocks, bk=blocks)
        return jnp.sum(out * ct)

    def loss_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal) * ct)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, r in zip(("dq", "dk", "dv"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_attention_forward_matches_ref(case):
    """The padded/custom_vjp forward path (not just the raw kernel), and
    the logsumexp residual it saves for the backward."""
    from repro.kernels.flash_attention import (attention_ref,
                                               attention_ref_lse,
                                               flash_attention)
    from repro.kernels.flash_attention.ops import _fwd
    b, h, kvh, sq, skv, d, causal, blocks = case
    q = _rand((b, h, sq, d))
    k = _rand((b, kvh, skv, d))
    v = _rand((b, kvh, skv, d))
    out = flash_attention(q, k, v, causal=causal, impl="interpret",
                          bq=blocks, bk=blocks)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    _, lse = _fwd(q, k, v, causal, skv - sq, True, blocks, blocks, True)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(attention_ref_lse(q, k,
                                                            causal=causal)),
                               atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_lse_matches_reference_normalizer(causal):
    from repro.kernels.flash_attention import attention_ref_lse
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    b, h, sq, skv, d = 1, 2, 128, 192, 32
    q = _rand((b, h, sq, d))
    k = _rand((b, h, skv, d))
    v = _rand((b, h, skv, d))
    _, lse = flash_attention_fwd(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True, save_residuals=True)
    ref = attention_ref_lse(q, k, causal=causal)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=1e-4)


def test_attention_core_kernel_path_matches_jnp():
    """Model-layer wiring: attention_core(impl='interpret') == direct core,
    forward and gradients, on the (b, s, h, hd) layout."""
    from repro.models.attention import (_broadcast_kv, attention_core,
                                        direct_attention)
    cfg = types.SimpleNamespace(n_heads=4, attn_impl="auto")
    b, s, h, kv, hd = 1, 128, 4, 2, 32
    q = _rand((b, s, h, hd))
    k = _rand((b, s, kv, hd))
    v = _rand((b, s, kv, hd))

    def loss_kernel(q, k, v):
        return jnp.sum(attention_core(cfg, q, k, v, causal=True,
                                      impl="interpret") ** 2)

    def loss_ref(q, k, v):
        kb, vb = _broadcast_kv(k, h), _broadcast_kv(v, h)
        return jnp.sum(direct_attention(q, kb, vb, causal=True) ** 2)

    np.testing.assert_allclose(float(loss_kernel(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=1e-5)
    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, r in zip(("dq", "dk", "dv"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-3, rtol=1e-3, err_msg=name)
