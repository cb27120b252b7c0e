"""flops.py against counts made by hand."""

from chipbench import flops, harness, peaks

BERT = harness.load_json(harness.HERE, "configs", "bert-base.json")
MISTRAL_LAYER = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "head_dim": 128, "vocab_size": 32000}


def test_bert_base_at_512_by_hand():
    # one layer, one token, forward: fused QKV 2*768*2304, output
    # 2*768*768, MLP 2*2*768*3072, QK^T and AV 2*512*768 each
    layer = 3538944 + 1179648 + 9437184 + 786432 + 786432
    assert layer == 15728640
    forward = 12 * layer + 2 * 768 * 30522
    assert forward == 235625472
    assert flops.encoder_forward_flops_per_token(BERT, 512) == forward
    assert flops.encoder_train_flops_per_token(BERT, 512) == 706876416


def test_one_mistral_layer_by_hand():
    # QKV 2*4096*(32+16)*128, output 2*4096*4096, gated MLP
    # 3*2*4096*14336, attention over 1000 cached positions 4*1000*32*128
    want = 50331648 + 33554432 + 352321536 + 16384000
    assert flops.decoder_layer_flops_per_token(MISTRAL_LAYER, 1000) == want
    assert flops.decoder_head_flops_per_token(MISTRAL_LAYER) == 262144000


def test_flash_kernels_by_hand():
    # 32 rows x 12 heads, 512 x 512, head 64, float32
    bh, t, d = 384, 512, 64
    ops, moved = flops.flash_attention_cost("fwd", bh, t, t, d, 4)
    assert ops == bh * 2 * 2 * t * t * d == 25769803776
    assert moved == bh * (4 * t * d * 4 + t * 4) == 202113024
    assert flops.flash_attention_cost("dq", bh, t, t, d, 4)[0] == ops * 3 // 2
    assert flops.flash_attention_cost("dkv", bh, t, t, d, 4)[0] == ops * 2
    chip = peaks.peaks_for("TPU v5 lite")
    # bandwidth-bound at head 64 in float32: 202 MB / 819 GB/s = 0.247 ms
    # against 25.8 GFLOP / 197 TFLOP/s = 0.131 ms
    assert flops.least_time(ops, moved, chip) == moved / 819e9
    # and compute-bound at the same shapes in bfloat16 with head 128
    ops, moved = flops.flash_attention_cost("dkv", bh, t, t, 128, 2)
    assert flops.least_time(ops, moved, chip) == ops / 197e12


def test_unknown_chip_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_prefill_is_the_sum_over_its_tokens():
    cfg = dict(MISTRAL_LAYER, num_hidden_layers=16)
    by_token = 16 * sum(flops.decoder_layer_flops_per_token(cfg, p)
                        for p in range(1, 701))
    assert flops.decoder_prefill_flops(cfg, 700) == by_token
