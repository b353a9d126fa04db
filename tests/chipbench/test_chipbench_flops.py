"""Required operations and bytes against hand counts, and the peaks table."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import flops, peaks  # noqa: E402

# d 4, 2 query heads and 1 kv head of 2, d_ff 6, vocab 10, one layer.
ATTN = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim_": 2, "d_ff": 6, "vocab": 10,
        "n_layers": 1, "pattern": ["attn"]}
# d 4, 2 heads of 2; one sLSTM and one mLSTM layer.
XLSTM = {"d_model": 4, "n_heads": 2, "n_kv_heads": 2, "d_ff": 0, "vocab": 10, "n_layers": 2,
         "pattern": ["slstm", "mlstm"]}


def test_attention_block_by_hand():
    # weights: wq 4*2*2=16, wk 4*1*2=8, wv 8, wo 16 -> 48; mlp 3*4*6 = 72
    assert flops.layer_params(ATTN, "attn") == {"attention": 48, "mlp": 72}
    # S = 3: a token sees (1+2+3)/3 = 2 keys on average; q.k and p.v cost
    # 2*hd each per key and head: 2 keys * 2 heads * 2 products * 4 = 32.
    assert flops.forward_flops_per_token(ATTN, "attn", 3) == {"attention": 96 + 32, "mlp": 144}
    # head 2*4*10 = 80; training is 3x forward
    assert flops.train_flops_per_token(ATTN, 3) == 3 * (80 + 128 + 144)
    assert flops.scope_flops_per_step(ATTN, "mlp", 2, 3) == 3 * 144 * 6
    # weights 48 * (2 + 2 + 4) bytes; activations 2 bytes * d * 6 tokens, in and out, both ways
    assert flops.scope_bytes_per_step(ATTN, "attention", 2, 3) == 48 * 8 + 2 * 4 * 6 * 4


def test_xlstm_blocks_by_hand():
    # mLSTM: q, k, v 3*4*2*2 = 48, gates 2*4*2 = 16, o-gate and out 2*16 = 32
    assert flops.layer_params(XLSTM, "mlstm") == {"mlstm": 96}
    # sLSTM: input 4*4*4 = 64, recurrent 4*2*2*2 = 32, out 16
    assert flops.layer_params(XLSTM, "slstm") == {"slstm": 112}
    # mLSTM memory per head: k v^T in and q^T C out, 2*hd^2 each: 2 heads * 2 * 8 = 32
    assert flops.forward_flops_per_token(XLSTM, "mlstm", 5) == {"mlstm": 192 + 32}
    assert flops.forward_flops_per_token(XLSTM, "slstm", 5) == {"slstm": 224}
    assert flops.train_flops_per_token(XLSTM, 5) == 3 * (80 + 224 + 224)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
