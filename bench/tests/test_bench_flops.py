"""Operation and byte counts against hand-computed values."""
import pytest

from bench import archs
from bench import flops as F

D = archs.of({"model": "dense_gqa"})

M = {"n_layers": 2, "d_model": 8, "d_ff": 16, "vocab_size": 10,
     "n_heads": 4, "n_kv_heads": 2, "head_dim": 2}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_layer_and_token_flops():
    # q 8*4*2, k and v 2*8*2*2, o 4*2*8, mlp 3*8*16 multiply-adds
    assert D.layer_matmul_flops(M) == 2 * (64 + 64 + 64 + 384)
    # one token at position 0: one key per layer, plus the output head
    one = 2 * (D.layer_matmul_flops(M) + 4 * 4 * 2 * 1) + 2 * 8 * 10
    assert D.tokens_flops(M, 0, 1, logits=True) == one
    # three tokens at positions 5, 6, 7 attend to 6 + 7 + 8 keys
    three = 2 * (3 * D.layer_matmul_flops(M) + 4 * 4 * 2 * 21)
    assert D.tokens_flops(M, 5, 3, logits=False) == three
    assert D.tokens_flops(M, 5, 0, logits=True) == 0.0


def test_ragged_call_and_roofline():
    # one slot: 3 queries against 20 keys in blocks of 16 -> 2 blocks
    flops, nbytes = D.attn_call(M, [(3, 20)], 16)
    assert flops == 4 * 4 * 2 * 3 * 20
    per_block = 16 * (2 * 2 * 2 * 2 + 4)
    assert nbytes == 2 * per_block + 2 * 3 * 4 * 2 * 2
    t, bound = F.least_time(flops, nbytes, PEAKS)
    assert bound == "memory" and t == pytest.approx(nbytes / 10.0)
    t, bound = F.least_time(1e6, 1.0, PEAKS)
    assert bound == "compute" and t == pytest.approx(1e4)
