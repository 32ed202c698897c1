"""FLOPs per token and codec bytes, against counts made by hand."""
import common
import counts

QWEN = common.read_json(common.BENCH / "configs" / "qwen1.5-0.5b.json")
STARCODER = common.read_json(common.BENCH / "configs" / "starcoder2-3b.json")


def test_qwen_layer_is_the_codec_cells_tree():
    s = common.dense_sizes(QWEN)
    shapes = counts.layer_leaf_shapes(s)
    # q, k, v, o: 4 x 1024^2; q, k, v biases: 3 x 1024; gate, up, down:
    # 3 x 1024 x 2816; two RMSNorm scales: 2 x 1024
    assert len(shapes) == 12
    assert counts.layer_coords(s) == 4 * 1024**2 + 3 * 1024 + 3 * 1024 * 2816 + 2 * 1024
    assert counts.layer_coords(s) == 12_850_176


def test_qwen_params_and_flops_by_hand():
    s = common.dense_sizes(QWEN)
    # 24 layers of 12,850,176, a tied 151936 x 1024 embedding, final norm
    assert counts.model_params(s) == 24 * 12_850_176 + 151936 * 1024 + 1024
    matmul = 24 * (4 * 1024**2 + 3 * 1024 * 2816) + 151936 * 1024
    assert matmul == 463_863_808
    # attention: QK^T and PV, 2 FLOPs per MAC, 16 x 64 wide, mean causal
    # context (2048 + 1) / 2, in each of 24 layers
    attn = 24 * 2 * 2 * 1024 * 1024.5
    assert counts.forward_flops_per_token(s, 2048) == 2 * matmul + attn
    assert counts.train_flops_per_token(s, 2048) == 3 * (2 * matmul + attn)
    assert round(counts.train_flops_per_token(s, 2048) / 1e9, 3) == 3.085


def test_starcoder_four_layers_by_hand():
    s = common.dense_sizes(STARCODER)
    layer = 2 * 3072**2 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert counts.layer_matmul_params(s) == layer
    matmul = 4 * layer + 49152 * 3072
    attn = 4 * 2 * 2 * 3072 * 1024.5
    assert counts.train_flops_per_token(s, 2048) == 3 * (2 * matmul + attn)


def test_codec_bytes():
    # 16-bit fields: two per int32 word, 2 bytes a coordinate each way
    assert counts.codec_min_bytes(10, 16) == 10 * (4 + 4 + 2 + 2)
    # 24-bit fields: one per word
    assert counts.codec_min_bytes(10, 24) == 10 * (4 + 4 + 4 + 4)
