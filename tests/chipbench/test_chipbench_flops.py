"""Required operations and bytes against hand counts and against the granite
cell's pinned yardstick, and the peaks table."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import flops, harness, peaks  # noqa: E402
from chipbench.reference import layout, transformer, xlstm  # noqa: E402

# d 4, 2 query heads and 1 kv head of 2, d_ff 6, vocab 10, one layer.
ATTN = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim_": 2, "d_ff": 6, "vocab": 10,
        "n_layers": 1, "pattern": ["attn"]}
# d 4, 2 heads of 2; one sLSTM and one mLSTM layer.
XLSTM = {"d_model": 4, "n_heads": 2, "n_kv_heads": 2, "d_ff": 0, "vocab": 10, "n_layers": 2,
         "pattern": ["slstm", "mlstm"]}


def test_attention_block_by_hand():
    # weights: wq 4*2*2=16, wk 4*1*2=8, wv 8, wo 16 -> 48; mlp 3*4*6 = 72
    assert transformer.layer_params(ATTN, "attn", "mlp") == {"attention": 48, "mlp": 72}
    # S = 3: a token sees (1+2+3)/3 = 2 keys on average; q.k and p.v cost
    # 2*hd each per key and head: 2 keys * 2 heads * 2 products * 4 = 32.
    assert transformer.forward_flops_per_token(ATTN, "attn", "mlp", 3) == {"attention": 96 + 32, "mlp": 144}
    # head 2*4*10 = 80; training is 3x forward
    assert flops.train_flops_per_token(transformer, ATTN, 3) == 3 * (80 + 128 + 144)
    assert flops.scope_flops_per_step(transformer, ATTN, "mlp", 2, 3) == 3 * 144 * 6
    # weights 48 * (2 + 2 + 4) bytes; activations 2 bytes * d * 6 tokens, in and out, both ways
    assert flops.scope_bytes_per_step(transformer, ATTN, "attention", 2, 3) == 48 * 8 + 2 * 4 * 6 * 4


def test_xlstm_blocks_by_hand():
    # mLSTM: q, k, v 3*4*2*2 = 48, gates 2*4*2 = 16, o-gate and out 2*16 = 32
    assert xlstm.layer_params(XLSTM, "mlstm", "none") == {"mlstm": 96}
    # sLSTM: input 4*4*4 = 64, recurrent 4*2*2*2 = 32, out 16
    assert xlstm.layer_params(XLSTM, "slstm", "none") == {"slstm": 112}
    # mLSTM memory per head: k v^T in and q^T C out, 2*hd^2 each: 2 heads * 2 * 8 = 32
    assert xlstm.forward_flops_per_token(XLSTM, "mlstm", "none", 5) == {"mlstm": 192 + 32}
    assert xlstm.forward_flops_per_token(XLSTM, "slstm", "none", 5) == {"slstm": 224}
    assert flops.train_flops_per_token(xlstm, XLSTM, 5) == 3 * (80 + 224 + 224)


def test_stack_is_laid_out_as_the_program_does():
    """A dense prefix, whole repeats of the pattern in the scan, and a remainder;
    the FFN kinds as the program names them."""
    cfg = dict(ATTN, n_layers=6, first_dense=1, pattern=["attn", "local"])
    assert [(e.group, e.name, e.kind, e.ffn, e.repeats) for e in layout.stack(cfg)] == [
        ("prefix", "layer0", "attn", "dense_mlp", None),
        ("scan", "block0", "attn", "mlp", 2), ("scan", "block1", "local", "mlp", 2),
        ("remainder", "layer5", "local", "mlp", None),
    ]
    assert sorted(flops.layer_kinds(cfg)) == sorted([("attn", "dense_mlp")] + [("attn", "mlp")] * 2
                                                    + [("local", "mlp")] * 3)
    assert [e.ffn for e in layout.stack(dict(cfg, n_experts=8))][:2] == ["dense_mlp", "moe"]
    assert {e.ffn for e in layout.stack(dict(XLSTM))} == {"none"}


#: The granite cell's yardstick as its parent computed it: a change to how the
#: counts are found may not move them.
GRANITE = {
    "train_flops_per_token": 2743123968.0,
    "flops": {"attention": 2886419349504.0, "mlp": 7730941132800.0},
    "bytes": {"attention": 939524096.0, "mlp": 2785017856.0},
}


@pytest.mark.parametrize("scope", ["attention", "mlp"])
def test_granite_yardstick_is_pinned(scope):
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    _, config, traffic = harness.resolve(bench, "train.granite-3-8b.s4096")
    fam, model = layout.family(config["reference"]), config["model"]
    B, S = int(traffic["batch"]), int(traffic["seq_len"])
    assert (B, S) == (1, 4096)
    assert flops.train_flops_per_token(fam, model, S) == GRANITE["train_flops_per_token"]
    assert flops.scope_flops_per_step(fam, model, scope, B, S) == GRANITE["flops"][scope]
    assert flops.scope_bytes_per_step(fam, model, scope, B, S) == GRANITE["bytes"][scope]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
