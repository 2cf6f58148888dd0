"""Operation and byte counts against hand counts at small shapes, and the
table of peaks."""
import pytest

from chipbench import peaks
from chipbench.metrics.lib import counts

# d=8, 2 query heads of 4, 1 KV head, d_ff=16, vocab 10, 2 layers
LM = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
      "intermediate_size": 16, "vocab_size": 10, "num_hidden_layers": 2}


def test_lm_per_token_counts():
    # q 8x8, k 8x4, v 8x4, o 8x8, up 8x16, down 16x8 multiply-adds
    assert counts.lm_matmul_flops_per_token(LM) == 2 * (64 + 32 + 32 + 64
                                                        + 128 + 128)
    # scores + weighted sum: 2 heads x 4 dims x 3 keys, twice, x2 ops
    assert counts.lm_attention_flops(LM, 3) == 2 * 2 * (2 * 4 * 3)
    assert counts.lm_head_flops(LM) == 2 * 8 * 10


def test_lm_prefill_and_decode():
    mm, head = 896.0, 160.0
    att = {k: 32.0 * k for k in range(1, 5)}     # 4 * 2 heads * 4 dims
    # 3 causal tokens attend to 1, 2, 3 keys; logits for the last only
    assert counts.lm_prefill_flops(LM, 3) == 2 * (3 * mm + att[1] + att[2]
                                                  + att[3]) + head
    assert counts.lm_prefill_flops(LM, 0) == 0
    assert counts.lm_decode_flops(LM, 3) == 2 * (mm + att[4]) + head
    assert counts.lm_request_flops(LM, 3, 2, 4) == (
        counts.lm_prefill_flops(LM, 3) + counts.lm_decode_flops(LM, 2)
        + counts.lm_decode_flops(LM, 3))


def test_mask_pack_cost():
    # (16, 256) bf16 map, (8, 128) blocks: 4 blocks, 3 live
    ops, byt = counts.mask_pack_cost(16, 256, 3, 8, 128, 2)
    assert ops == 16 * 256
    assert byt == 16 * 256 * 2 + 3 * 8 * 128 * 2 + 1


def test_spmm_cs_cost():
    # (16, 256) x (256, 32); 4 blocks, all live: both block-columns read
    ops, byt = counts.spmm_cs_cost(16, 256, 32, 4, 8, 128, 2)
    assert ops == 2 * 4 * 8 * 128 * 32
    assert byt == 4 * 8 * 128 * 2 + 1 + 256 * 32 * 2 + 16 * 32 * 4
    # nothing live: no operations, no weight rows, the output still written
    ops, byt = counts.spmm_cs_cost(16, 256, 32, 0, 8, 128, 2)
    assert ops == 0 and byt == 1 + 16 * 32 * 4


def test_live_columns():
    assert counts.live_columns(256, 128, 2, 0.5) == 2 * (1 - 0.25)
    assert counts.live_columns(256, 128, 3, 0.0) == 2


def test_roofline_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 50, peak) == (10.0, "compute")
    assert counts.roofline_seconds(10, 50, peak) == (5.0, "memory")


def test_resnet_flops_hand_count():
    c = {"image_hw": 8, "in_channels": 3, "stem_kernel": 3,
         "stage_blocks": [1, 1], "stage_channels": [4, 8], "num_classes": 5}
    stem = 2 * 3 * 9 * 4 * 64
    b0 = 2 * (2 * 4 * 9 * 4 * 64)                      # two 3x3 4->4 at 8x8
    b1 = (2 * 4 * 9 * 8 * 16 + 2 * 8 * 9 * 8 * 16     # 3x3 s2 4->8, 8->8 at 4x4
          + 2 * 4 * 8 * 16)                            # 1x1 s2 projection
    assert counts.resnet_flops_per_image(c) == stem + b0 + b1 + 2 * 8 * 5


def test_resnet18_tiny_imagenet_is_about_4_4_gflop():
    import json
    import pathlib
    c = json.loads((pathlib.Path(__file__).parents[1] / "configs"
                    / "resnet18-tin.json").read_text())
    assert 4.3e9 < counts.resnet_flops_per_image(c) < 4.5e9


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
