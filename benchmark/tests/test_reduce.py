"""The trace reduction: on a hand-made trace whose answers are known, and on
a small trace recorded on the chip (``data/``, PR 23)."""
import gzip
import json
import os

import pytest

from benchmark import reduce
from benchmark.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6          # nanoseconds


def hand_made() -> Trace:
    """One chip, a window of 100 ms holding two steps of 40 ms and 20 ms of
    idle; each step is a ``while`` that spans a 10 ms kernel and a 25 ms
    copy (5 ms of the loop are its own)."""
    ops = []
    for base in (5 * MS, 55 * MS):
        ops += [("while.1", "", base, 40 * MS),
                ("flash_attention_fwd.7", "bf16[32,1024,128]",
                 base + 2 * MS, 10 * MS),
                ("copy.14", "bf16[16,32,12288,128]", base + 13 * MS,
                 25 * MS)]
    host = [("window", 0.0, 100 * MS), ("make_batch", 0.0, 4 * MS),
            ("train_batch", 4 * MS, 42 * MS), ("make_batch", 46 * MS, 8 * MS),
            ("train_batch", 54 * MS, 46 * MS)]
    return Trace({"/device:TPU:0": ops}, host)


def test_self_time_does_not_count_a_loop_body_twice():
    t = hand_made()
    selfs = reduce.self_times(t.devices["/device:TPU:0"])
    by = {}
    for name, _, ns in selfs:
        by[name] = by.get(name, 0) + ns
    assert by == {"while.1": 10 * MS, "flash_attention_fwd.7": 20 * MS,
                  "copy.14": 50 * MS}


def test_busy_idle_and_kernel_time():
    t = hand_made()
    busy, window = reduce.busy_and_window_s(t)
    assert busy == pytest.approx(0.080) and window == pytest.approx(0.100)
    obs = {"trace": t, "counters": {"traced_steps": 2}, "clocks": {}}
    assert reduce.idle_share({}, obs) == pytest.approx(20.0)
    per_step = reduce.scope_time({"match": ["flash_attention_fwd"],
                                  "per": "traced_steps", "scale": 1000.0},
                                 obs)
    assert per_step == pytest.approx(10.0)                 # ms a step
    assert reduce.scope_time({"match": ["paged_attention"],
                              "per": "traced_steps"}, obs) is None


def test_breakdown_names_ops_and_gaps():
    t = hand_made()
    top = reduce.top_ops(t)
    assert [name for name, _ in top] == [
        "copy.14 bf16[16,32,12288,128]",
        "flash_attention_fwd.7 bf16[32,1024,128]", "while.1"]
    assert top[0][1] == pytest.approx(0.050)
    gaps = dict(reduce.idle_gaps(t))
    # 5 ms before the first step and 10 between the steps (their middles
    # lie under make_batch), 5 after the last op (under train_batch)
    assert sum(gaps.values()) == pytest.approx(0.020)
    assert gaps["make_batch"] == pytest.approx(0.015)
    assert gaps["train_batch"] == pytest.approx(0.005)


def test_split_hlo_takes_name_and_result_shape():
    from benchmark.trace import split_hlo
    assert split_hlo(
        "%copy.39.remat = bf16[16,32,12288,128]{3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[16,32,12288,128]{3,1,2,0:T(8,128)(2,1)} %fusion.138)"
    ) == ("copy.39.remat", "bf16[16,32,12288,128]")
    assert split_hlo(
        "%flash_attention_fwd.12 = (bf16[32,1024,128]{2,1,0:T(8,128)(2,1)"
        "S(1)}, f32[32,1,1024]{2,1,0:T(1,128)}) custom-call(bf16[32,1024,128]"
        "{2,1,0} %bitcast.477), custom_call_target=\"tpu_custom_call\""
    ) == ("flash_attention_fwd.12", "bf16[32,1024,128]")
    assert split_hlo("%while.329 = (s32[]{:T(128)}, f32[]{:T(128)}) "
                     "while(%tuple.1)") == ("while.329", "s32[]")
    assert split_hlo("jit_train_step(123)") == ("jit_train_step(123)", "")


def test_readers_return_nothing_without_a_trace():
    obs = {"trace": None, "counters": {}, "clocks": {},
           "context": {"peaks": None}}
    defs = [{"name": "x", "unit": "ms", "reducer": r, "args": a} for r, a in
            [("idle_share", {}), ("scope_time", {"match": ["k"], "per": "n"}),
             ("clock_median", {"clock": "c"}), ("counter", {"name": "n"}),
             ("counter_ratio", {"num": "a", "den": "b"}),
             ("train_mfu", {"rate": "r"})]]
    assert reduce.layer_metrics(defs, obs) == {}


@pytest.mark.parametrize("name,kernels,steps_span", [
    ("trace_train.json.gz", ["flash_attention_fwd", "flash_attention_bwd_dq",
                             "flash_attention_bwd_dkv"], "train_batch"),
    ("trace_serve.json.gz", ["paged_attention"], "step"),
])
def test_recorded_chip_trace(name, kernels, steps_span):
    t = Trace.from_json(json.load(gzip.open(os.path.join(DATA, name), "rt")))
    assert t.devices and reduce.window_of(t) is not None
    busy, window = reduce.busy_and_window_s(t)
    assert 0 < busy <= window
    assert any(s[0] == steps_span for s in t.host)
    for k in kernels:
        assert reduce.scope_seconds(t, [k]) > 0
    top = reduce.top_ops(t)
    assert 0 < len(top) <= 10 and top[0][1] >= top[-1][1] > 0
    # self times never add up to more than the busy time
    total = sum(ns for ops in t.devices.values()
                for _, _, ns in reduce.self_times(ops))
    spans = sum(b - a for ops in t.devices.values()
                for a, b in reduce.busy_intervals(ops, 0, float("inf")))
    assert total <= spans * (1 + 1e-9)
