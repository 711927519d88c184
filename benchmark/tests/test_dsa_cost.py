"""Needed operations and bytes of DeepSeek Sparse Attention over a latent
cache, from the program's ``sparse.*`` and ``mla.*`` counters, at the
DeepSeek-V3.2-Exp cell's sizes, against counts worked by hand."""
import pytest

from benchmark import dsa_cost, harness, mla_cost, moe_cost

CELL = harness.load_cell("serve-deepseek-v32-exp-ep16-l5-longdoc")
DIMS = harness.load_family("deepseek_v32").dims(CELL.config)
PEAKS = harness.peaks_for("TPU v5 lite")
#: one layer of a decode call: 24 lanes at 8192 cached tokens each
DECODE = {"sparse.rows_sum": 24, "sparse.keys_scored_sum": 24 * 8192,
          "sparse.keys_selected_sum": 24 * 2048,
          "sparse.pages_walked_sum": 24 * 256, "mla.rows_sum": 24,
          "mla.ctx_tokens_sum": 24 * 8192, "mla.selected_keys_sum": 24 * 2048}
#: one layer of a chunk of 1536: rows 6656 .. 8191 of one lane
PAIRS = sum(range(6657, 8193))
CHUNK = {"sparse.rows_sum": 1536, "sparse.keys_scored_sum": PAIRS,
         "sparse.keys_selected_sum": 1536 * 2048,
         "sparse.pages_walked_sum": 256, "mla.rows_sum": 1536,
         "mla.ctx_tokens_sum": PAIRS, "mla.selected_keys_sum": 1536 * 2048,
         "mla.chunk_selected_keys_sum": 1536 * 2048,
         "mla.chunk_keys_sum": 8192}


def test_the_dims_are_the_published_latent_and_indexer():
    assert (DIMS["heads"], DIMS["kv_lora_rank"], DIMS["qk_nope_head_dim"],
            DIMS["qk_rope_head_dim"], DIMS["v_head_dim"], DIMS["index_heads"],
            DIMS["index_head_dim"], DIMS["index_topk"]) == (
        128, 512, 128, 64, 128, 64, 128, 2048)
    assert (DIMS["experts"], DIMS["router_outputs"], DIMS["layers"],
            DIMS["dense_layers"]) == (16, 256, 5, 1)
    assert mla_cost.pair_flops(DIMS) == (278528.0, 81920.0, 33554432.0)


def test_a_decode_row_needs_its_selected_rows_alone():
    flops, moved = dsa_cost.attention(DECODE, DIMS)
    assert flops == 24 * 2048 * 278528
    assert moved == 2 * (576 * 24 * 2048 + 128 * (576 + 512) * 24)
    # a quarter of what the dense walk over 8192 tokens needs
    dense = mla_cost.attention(DECODE, DIMS)
    assert flops / dense[0] == 0.25 and moved / dense[1] < 0.28
    # 2 x 64 x 128 operations a scored key, each live indexer key once
    flops, moved = dsa_cost.KERNELS["sparse_index_scores"](DECODE, DIMS, 32)
    assert flops == 16384 * 24 * 8192
    assert moved == 24 * 256 * 32 * 128 * 2 + 24 * 64 * (128 * 2 + 4) \
        + 4 * 24 * 8192
    assert moe_cost.roofline(flops, moved, 1.0, PEAKS)["bound"] == "memory"
    assert dsa_cost.KERNELS["sparse_topk"](DECODE, DIMS, 32) == (
        24 * 8192.0, 4.0 * 24 * 8192 + 8 * 24)


def test_a_chunk_needs_the_smaller_form_over_its_selected_pairs():
    flops, moved = dsa_cost.attention(CHUNK, DIMS)
    pairs = 1536 * 2048
    expanded = 81920 * pairs + 33554432 * 8192
    assert flops == expanded < 278528 * pairs
    # the rows' selections cover the call's keys: each row read once
    assert moved == 2 * (576 * 8192 + 128 * 1088 * 1536)
    assert moe_cost.roofline(flops, moved, 1.0, PEAKS)["bound"] == "compute"
    # under the top-k every pair is selected: mla_cost's own count
    under = dict(CHUNK, **{"mla.selected_keys_sum": PAIRS,
                           "mla.chunk_selected_keys_sum": PAIRS,
                           "mla.chunk_ctx_tokens_sum": PAIRS})
    assert dsa_cost.attention(under, DIMS) == mla_cost.attention(under, DIMS)


def test_a_step_with_both_calls_adds_them():
    both = {k: DECODE.get(k, 0) + CHUNK.get(k, 0) for k in dsa_cost.COUNTERS}
    f_d, m_d = dsa_cost.attention(DECODE, DIMS)
    f_c, m_c = dsa_cost.attention(CHUNK, DIMS)
    assert dsa_cost.attention(both, DIMS) == (f_d + f_c, m_d + m_c)


@pytest.mark.parametrize("metric, kernels", [
    ("dsa_attention_ms_per_step", ["paged_attention_latent"]),
    ("dsa_select_ms_per_step", ["sparse_index_scores", "sparse_topk"])])
def test_the_cell_expects_the_kernels_the_roofline_reads(metric, kernels):
    m = harness.load_json(f"{harness.HERE}/layer_metrics/{metric}.json")
    assert m["args"]["match"] == kernels and m["workloads"] == [CELL.name]
    assert set(kernels) <= set(CELL.expect_kernels) \
        and set(kernels) <= set(dsa_cost.KERNELS)
    from deepspeed_tpu.ops.pallas.latent_attention import KERNEL_NAME
    assert KERNEL_NAME in dsa_cost.KERNELS
