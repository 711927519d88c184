"""Needed operations and bytes of a layer with an indexer, from the
program's ``sparse.*`` counters, at the Keye-VL-2.0 cell's sizes."""
import pytest

from benchmark import harness, moe_cost, sparse_cost

CELL = harness.load_cell("serve-keye-vl2-30b-ep8-l8-longdoc")
DIMS = harness.load_family("keye_vl2").dims(CELL.config)
PEAKS = harness.peaks_for("TPU v5 lite")
#: one layer of a decode call: 12 lanes at 8192 keys each
DECODE = {"sparse.rows_sum": 12, "sparse.keys_scored_sum": 12 * 8192,
          "sparse.keys_selected_sum": 12 * 2048,
          "sparse.pages_walked_sum": 12 * 256}
#: one layer of a chunk: rows 7936 .. 8191 of one lane
CHUNK = {"sparse.rows_sum": 256,
         "sparse.keys_scored_sum": sum(range(7937, 8193)),
         "sparse.keys_selected_sum": 256 * 2048,
         "sparse.pages_walked_sum": 256}


def test_the_dims_are_the_published_indexer():
    assert (DIMS["index_heads"], DIMS["index_head_dim"],
            DIMS["index_topk"]) == (16, 64, 2048)
    assert (DIMS["heads"], DIMS["kv_heads"], DIMS["head_dim"]) == (32, 4, 128)


def test_index_scores_count_a_pair_once_and_a_chunks_keys_once():
    flops, moved = sparse_cost.index_scores(DECODE, DIMS, 32)
    assert flops == 2 * 16 * 64 * 12 * 8192
    assert moved == 12 * 8192 * 64 * 2 + 12 * 16 * (64 * 2 + 4) \
        + 4 * 12 * 8192
    f2, m2 = sparse_cost.index_scores(CHUNK, DIMS, 32)
    # a chunk's 256 rows score 21 times the pairs over a twelfth of the keys
    assert f2 / flops == pytest.approx(CHUNK["sparse.keys_scored_sum"]
                                       / (12 * 8192))
    assert m2 < f2 / 400


def test_topk_reads_every_score_once():
    ops, moved = sparse_cost.topk(DECODE)
    assert ops == 12 * 8192 and moved == 4 * 12 * 8192 + 8 * 12
    assert moe_cost.roofline(ops, moved, 1e-4, PEAKS)["bound"] == "memory"


def test_attention_needs_the_selected_keys_and_no_more_than_the_pages_hold():
    flops, moved = sparse_cost.attention(DECODE, DIMS, 32)
    assert flops == 4 * 32 * 128 * 12 * 2048
    assert moved == 2 * (2 * 4 * 128 * 12 * 2048 + 2 * 32 * 128 * 12)
    # a decode row needs a quarter of what walking its 8192 keys reads
    assert moved < 0.26 * 2 * 2 * 4 * 128 * 12 * 8192
    f2, m2 = sparse_cost.attention(CHUNK, DIMS, 32)
    assert m2 == 2 * (2 * 4 * 128 * 8192 + 2 * 32 * 128 * 256)
    assert moe_cost.roofline(f2, m2, 1e-3, PEAKS)["bound"] == "compute"


def test_every_kernel_of_the_cell_has_its_cost():
    assert set(sparse_cost.KERNELS) <= set(CELL.expect_kernels)
    for needed in sparse_cost.KERNELS.values():
        flops, moved = needed(DECODE, DIMS, 32)
        assert flops > 0 and moved > 0
