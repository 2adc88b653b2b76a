"""The flash kernels' block rule and causal block geometry, at the shapes
the benchmark cells run: pure arithmetic, nothing compiles."""
import pytest

from repro.kernels.common import round_up
from repro.kernels.flash_attention.kernel import Blocks, vmem_bytes
from repro.kernels.flash_attention.ops import (MAX_BLOCK, VMEM_BUDGET,
                                               block_sizes)

def n_live(blocks):
    return sum(bool(blocks.live(i, j))
               for i in range(blocks.nq) for j in range(blocks.nk))


SHAPES = [
    # sq, skv, head_dim, group
    (1024, 1024, 64, 1),     # gpt2-medium training, b8 h16
    (64, 64, 128, 3),        # phi4-mini prefill, h24 over kv8
    (512, 512, 128, 3),
    (2048, 2048, 128, 3),
    (1500, 1500, 64, 1),     # unaligned (Whisper-style 1500 frames)
    (448, 1500, 64, 1),      # cross-attention: text queries, audio keys
]


@pytest.mark.parametrize("sq,skv,d,group", SHAPES)
def test_blocks_divide_fit_and_stay_in_shape(sq, skv, d, group):
    bq, bk = block_sizes(sq, skv, d, group)
    for n, blk in ((sq, bq), (skv, bk)):
        padded = round_up(n, blk)
        assert padded % blk == 0
        assert blk <= min(MAX_BLOCK, round_up(n, 16))
        assert blk % 128 == 0 or blk == padded      # a legal (1, blk) row
        assert padded - n <= max(n // 8, 15)
    for kind in ("fwd", "dkv", "dq"):
        assert vmem_bytes(kind, bq, bk, d, group, 2) <= VMEM_BUDGET


def test_blocks_at_the_cells_shapes():
    assert block_sizes(1024, 1024, 64, 1) == (512, 512)
    assert block_sizes(64, 64, 128, 3) == (64, 64)
    assert block_sizes(2048, 2048, 128, 3) == (512, 512)
    assert block_sizes(1500, 1500, 64, 1) == (512, 512)


@pytest.mark.parametrize("s,blk,live", [
    (1024, 128, 36), (1024, 512, 3), (2048, 512, 10)])
def test_causal_live_pairs(s, blk, live):
    n = s // blk
    blocks = Blocks(blk, blk, n, n, True, 0)
    assert n_live(blocks) == live
    # a dead step fetches a live block: no DMA of its own
    for qi in range(n):
        for ki in range(n):
            assert blocks.live(qi, int(blocks.kv_block(qi, ki)))
            assert blocks.live(int(blocks.q_block(ki, qi)), ki)


def test_live_pairs_count_q_offset_and_kv_len():
    # prefill continuation: 1024 queries after 256 cached keys
    assert n_live(Blocks(512, 256, 2, 5, True, 256)) == 3 + 5
    # padded keys: 1000 valid of 1536
    assert n_live(Blocks(512, 512, 3, 3, False, 0, kv_len=1000)) == 6
    assert n_live(Blocks(512, 512, 3, 3, True, 0, kv_len=1000)) == 5
    # diagonal and kv_len-edge blocks keep the mask; interior ones do not
    b = Blocks(512, 512, 3, 3, True, 0, kv_len=1000)
    assert b.masked(0, 0) and b.masked(1, 1) and b.masked(2, 1)
    assert not b.masked(1, 0) and not b.masked(2, 0)
