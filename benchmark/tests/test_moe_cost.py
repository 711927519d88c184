"""Needed operations and bytes of the expert matmuls, the roofline share,
and the reader that joins a trace's kernel time with the program's counters
(on a hand-made trace and ring)."""
import pytest

from benchmark import harness, moe_cost, moe_roofline, spans
from benchmark import trace as tracing

OLMOE = dict(hidden=2048, width=1024)
PEAKS = harness.peaks_for("TPU v5 lite")


def test_needed_counts_a_row_once_and_an_idle_expert_not_at_all():
    # a decode step of the cell: 56 real tokens x 8 over 64 experts, 3 idle
    sizes = [0, 0, 0] + [7] * 60 + [28]
    assert sum(sizes) == 448
    flops, moved = moe_cost.needed_from_groups(sizes, **OLMOE)
    assert flops == 2 * 3 * 448 * 2048 * 1024
    assert moved == 2 * (61 * 3 * 2048 * 1024 + 2 * 448 * 2048)
    # the same rows over fewer experts need fewer bytes, the same operations
    f2, m2 = moe_cost.needed(448, 8, **OLMOE)
    assert f2 == flops and m2 < moved / 7
    # an ungated expert has two matrices
    assert moe_cost.needed(448, 61, matrices=2, **OLMOE)[0] == flops * 2 / 3


def test_roofline_names_its_bound_and_passes_100_only_if_overcounted():
    flops, moved = moe_cost.needed(512, 64, **OLMOE)
    least = moved / 819e9                       # 0.98 ms: the weights
    r = moe_cost.roofline(flops, moved, 1.17e-3, PEAKS)
    assert r["bound"] == "memory"
    assert r["pct"] == pytest.approx(100 * least / 1.17e-3)
    assert 80 < r["pct"] < 90
    # many rows on one expert: compute bound
    r = moe_cost.roofline(*moe_cost.needed(65536, 1, **OLMOE), 1.0, PEAKS)
    assert r["bound"] == "compute"


def hand_made():
    """Three traced steps (7, 8, 9); 8 runs a prefill chunk. gmm ops of 1 ms
    each: 3 in step 7, 6 in step 8, 3 in step 9, one stray op outside."""
    ms = 1e6
    spans_ = [("serve.step", 0 * ms, 10 * ms, {"step": 7}, "t"),
              ("serve.step", 10 * ms, 20 * ms, {"step": 8}, "t"),
              ("serve.prefill", 11 * ms, 5 * ms, {"rid": 1}, "t"),
              ("serve.step", 30 * ms, 10 * ms, {"step": 9}, "t")]
    ops = [("gmm.1", "bf16[512,1024]", t * ms, 1 * ms)
           for t in (1, 2, 3, 12, 13, 14, 21, 22, 23, 31, 32, 33, 45)]
    ops.append(("fusion.9", "bf16[64,2048]", 4 * ms, 2 * ms))
    pt = spans.ProgramTrace(
        tracing.Trace({"/device:TPU:0": ops},
                      [(tracing.WINDOW_SPAN, 0.0, 50 * ms)]),
        {"/device:TPU:0": [""] * len(ops)}, spans_)
    gains = {"moe.assignments": 448 * 8, "moe.layer_steps": 8,
             "moe.experts_idle_sum": 2}
    chunk = {"moe.assignments": (448 + 256 * 8) * 8, "moe.layer_steps": 16,
             "moe.experts_idle_sum": 2}
    ring = [("serve.step", None, 0, 10 * ms, {"step": 7, "d": gains}),
            ("serve.prefill", "serve.step", 11 * ms, 16 * ms, {"rid": 1}),
            ("serve.step", None, 10 * ms, 30 * ms, {"step": 8, "d": chunk}),
            ("serve.step", None, 30 * ms, 40 * ms, {"step": 9, "d": gains}),
            ("serve.step", None, 40 * ms, 50 * ms, {"step": 10, "d": gains})]
    return pt, ring


def test_kernel_time_is_joined_to_steps_by_number():
    pt, ring = hand_made()
    assert moe_roofline.kernel_seconds_by_step(pt) == pytest.approx(
        {7: 3e-3, 8: 6e-3, 9: 3e-3})


def test_roofline_by_kind_splits_decode_from_chunk_steps():
    pt, ring = hand_made()
    dims = dict(experts=64, hidden=2048, mlp_dim=1024, mlp_matrices=3)
    out = moe_roofline.roofline_by_kind(pt, ring, dims, PEAKS)
    assert set(out) == {"decode", "chunk"}
    assert out["decode"]["steps"] == 2 and out["chunk"]["steps"] == 1
    assert out["decode"]["kernel_ms_per_step"] == pytest.approx(3.0)
    flops, moved = moe_cost.needed(448 * 8, 64 * 8 - 2, **OLMOE)
    want = moe_cost.roofline(flops, moved, 3e-3, PEAKS)
    assert out["decode"]["value"] == pytest.approx(want["pct"])
    assert out["decode"]["bound"] == "memory"
    assert out["chunk"]["kernel_ms_per_step"] == pytest.approx(6.0)


def test_a_program_without_the_counters_gives_nothing_and_does_not_raise():
    pt, ring = hand_made()
    bare = [(e[0], e[1], e[2], e[3], dict(e[4], d={})) if e[0] == "serve.step"
            else e for e in ring]
    dims = dict(experts=64, hidden=2048, mlp_dim=1024, mlp_matrices=3)
    assert moe_roofline.roofline_by_kind(pt, bare, dims, PEAKS) == {}
