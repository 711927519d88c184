"""Needed operations and bytes of latent attention, from the program's
``mla.*`` counters, at the DeepSeek-V2 cell's sizes, against counts worked
by hand."""
import pytest

from benchmark import harness, mla_cost, moe_cost

CELL = harness.load_cell("serve-deepseek-v2-ep8-l5-longdoc")
DIMS = harness.load_family("deepseek_v2").dims(CELL.config)
PEAKS = harness.peaks_for("TPU v5 lite")
#: one layer of a decode call: 24 lanes at 8192 cached tokens each
DECODE = {"mla.rows_sum": 24, "mla.ctx_tokens_sum": 24 * 8192,
          "mla.pages_walked_sum": 24 * 256}
#: one layer of a chunk of 512: rows 7680 .. 8191 of one lane
PAIRS = sum(range(7681, 8193))
CHUNK = {"mla.rows_sum": 512, "mla.ctx_tokens_sum": PAIRS,
         "mla.pages_walked_sum": 256, "mla.chunk_ctx_tokens_sum": PAIRS,
         "mla.chunk_keys_sum": 8192}


def test_the_dims_are_the_published_latent():
    assert (DIMS["heads"], DIMS["kv_lora_rank"], DIMS["qk_nope_head_dim"],
            DIMS["qk_rope_head_dim"], DIMS["v_head_dim"]) == (128, 512, 128,
                                                              64, 128)
    assert (DIMS["experts"], DIMS["router_outputs"]) == (20, 160)
    # 128 x 2 x (576 + 512); 128 x 2 x (192 + 128); 128 x 2 x 512 x 256
    assert mla_cost.pair_flops(DIMS) == (278528.0, 81920.0, 33554432.0)


def test_a_decode_row_needs_the_absorbed_count_and_each_row_once_a_lane():
    flops, moved = mla_cost.attention(DECODE, DIMS)
    assert flops == 24 * 8192 * 278528
    assert moved == 2 * (576 * 24 * 8192 + 128 * (576 + 512) * 24)
    # 1 152 bytes a cached token against 278 528 operations (241.8 a byte)
    # sit ON the v5e's ridge (197e12 / 819e9 = 240.5); with the rows' queries
    # in and outputs out, 234.8: the memory side binds, by a hair's breadth
    assert 278528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert flops / moved == pytest.approx(234.8, abs=0.1)
    assert PEAKS["bf16_tflops"] * 1e3 / PEAKS["hbm_gb_per_s"] == \
        pytest.approx(240.5, abs=0.1)
    assert moe_cost.roofline(flops, moved, 1.0, PEAKS)["bound"] == "memory"


def test_a_chunk_needs_the_smaller_form():
    flops, moved = mla_cost.attention(CHUNK, DIMS)
    absorbed = 278528 * PAIRS
    expanded = 81920 * PAIRS + 33554432 * 8192
    # at 512 rows over 8192 cached tokens the expanded form is the smaller:
    # 0.536 of the absorbed count
    assert flops == expanded < absorbed
    assert expanded / absorbed == pytest.approx(0.536, abs=0.001)
    assert moved == 2 * (576 * 8192 + 128 * 1088 * 512)
    assert moe_cost.roofline(flops, moved, 1.0, PEAKS)["bound"] == "compute"
    # a first chunk of 32 rows over its own 32 tokens: absorbed is smaller
    # (re-expanding 32 tokens costs more than the 528 pairs save)
    pairs = sum(range(1, 33))
    first = {"mla.rows_sum": 32, "mla.ctx_tokens_sum": pairs,
             "mla.chunk_ctx_tokens_sum": pairs, "mla.chunk_keys_sum": 32}
    assert mla_cost.attention(first, DIMS)[0] == 278528 * pairs \
        < 81920 * pairs + 33554432 * 32


def test_a_step_with_both_calls_adds_them():
    both = {k: DECODE.get(k, 0) + CHUNK.get(k, 0) for k in mla_cost.COUNTERS}
    f_d, m_d = mla_cost.attention(DECODE, DIMS)
    f_c, m_c = mla_cost.attention(CHUNK, DIMS)
    assert mla_cost.attention(both, DIMS) == (f_d + f_c, m_d + m_c)


def test_the_cell_expects_the_kernel_the_roofline_reads():
    from benchmark import mla_roofline
    assert mla_roofline.KERNEL in CELL.expect_kernels
    metric = harness.load_json(harness.HERE
                               + "/layer_metrics/mla_attention_ms_per_step.json")
    assert metric["args"]["match"] == [mla_roofline.KERNEL]
    from deepspeed_tpu.ops.pallas.latent_attention import KERNEL_NAME
    assert KERNEL_NAME == mla_roofline.KERNEL and "paged_attention" in \
        KERNEL_NAME
