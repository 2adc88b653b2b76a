"""bench/work.py against counts worked out by hand."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cells  # noqa: E402,F401

import pytest  # noqa: E402

from bench import harness, work  # noqa: E402
from bench.peaks import peak  # noqa: E402


def spec(name):
    return harness.decoder_spec(harness.read_json(os.path.join(
        tiny_cells.ROOT, "bench", "configs", name + ".json")))


def test_gpt2_medium_six_n_d():
    s = spec("gpt2-medium")
    # 24 layers x (4 d^2 attention + 2 d ff MLP) + tied head V d
    n = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 50257 * 1024
    assert work.matmul_params(s) == n == 353_453_056
    # + causal attention, 6 x layers x heads x hd x seq
    assert work.train_flops_per_token(s, 1024) == \
        6 * n + 6 * 24 * 16 * 64 * 1024 == 2_271_713_280


def test_phi4_mini_decode_and_paged_kernel():
    s = spec("phi4-mini")
    n = 32 * (3072 * 128 * (2 * 24 + 2 * 8) + 3 * 3072 * 8192) \
        + 200064 * 3072
    assert work.matmul_params(s) == n
    assert work.decode_flops(s, 1000) == 2 * n + 4 * 32 * 24 * 128 * 1000
    f, b = work.paged_attention(s, 1000)
    assert f == 4 * 32 * 24 * 128 * 1000
    # each key and value once (8 kv heads x 128 x bf16) + q in and out
    assert b == 32 * (2 * 1000 * 8 * 128 + 2 * 24 * 128) * 2
    t, bound = work.roofline_seconds(f, b, peak("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(b / 819e9)


@pytest.mark.parametrize("kind,products,extra", [
    ("fwd", 2, 0), ("dkv", 4, 1), ("dq", 3, 2)])
def test_flash_kernels(kind, products, extra):
    b, h, kv, s, d = 2, 4, 2, 8, 16
    f, nbytes = work.flash_attention(kind, batch=b, heads=h, kv_heads=kv,
                                     seq=s, head_dim=d)
    assert f == products * b * h * d * s * s
    q, k, rows = b * h * s * d * 2, b * kv * s * d * 2, b * h * s * 4
    want = {"fwd": 2 * q + 2 * k + rows,
            "dkv": 2 * q + 2 * k + 2 * rows + 2 * k,
            "dq": 2 * q + 2 * k + 2 * rows + q}[kind]
    assert nbytes == want


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peak("cpu")
