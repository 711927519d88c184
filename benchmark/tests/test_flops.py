"""The FLOPs arithmetic against numbers worked by hand."""
from benchmark import flops, harness


def dims(cell):
    c = harness.load_cell(cell).config
    return harness.load_family(c["family"]).dims(c)


def test_gpt2_1_3b():
    d = dims("train-gpt2-1.3b-z3")
    # a layer: qkv 2048x6144 + proj 2048x2048 + MLP 2 x 2048x8192
    layer = 12_582_912 + 4_194_304 + 33_554_432
    assert layer == 50_331_648
    head = 50257 * 2048                                   # 102,926,336
    assert flops.matmul_params(d) == 24 * layer + head == 1_310_885_888
    # 6 N + causal attention 6 L S heads head_dim at S = 1024
    attn = 6 * 24 * 1024 * 16 * 128                       # 301,989,888
    assert flops.train_flops_per_token(d, 1024) == \
        6 * 1_310_885_888 + attn == 8_167_305_216


def test_mistral_7b_l16():
    d = dims("serve-mistral-7b-l16-chat")
    # a layer: qkv 4096 x (32 + 2*8) x 128 + proj 4096x4096 + 3 x 4096x14336
    layer = 25_165_824 + 16_777_216 + 176_160_768
    assert layer == 218_103_808
    head = 32000 * 4096                                   # 131,072,000
    assert flops.matmul_params(d) == 16 * layer + head == 3_620_732_928
