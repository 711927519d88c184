"""``benchmark/spans.py``: the reader of what the program recorded. On a
hand-made trace whose answers are worked out by hand, on a hand-encoded
xplane, on a few steps recorded on the chip (``data/``, PR 24), and, for the
agreement of inside with outside, on a CPU rehearsal of the serve driver."""
import dataclasses
import gzip
import json
import os
import tempfile
import time

import pytest

from benchmark import harness, reduce, spans
from benchmark.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6          # nanoseconds
TPU = "/device:TPU:0"


def hand_made() -> spans.ProgramTrace:
    """One chip, a window of 100 ms, two serve steps.

    Step 0 (host 0..48): admit 1..4 (alloc 2..3), decode 5..47 with build
    5..9, dispatch 9..10, fetch 10..46, bookkeep 46..47. Device: busy
    10..45 (a ``while`` that spans a 20 ms copy under ``kv_write`` and a
    10 ms kernel under ``attend``; 5 ms are the loop's own, unscoped).
    Step 1 (host 50..98): the same, shifted by 50 ms.
    Idle: 0..10 (middle 5: decode.build starts AT 5, the deepest span
    holding 5.0), 45..60 (middle 52.5: step 1's admit.alloc 52..53), 95..100
    (middle 97.5: step 1, no child there)."""
    ops, names, prog = [], [], []
    for base in (0.0, 50 * MS):
        ops += [("while.1", "", base + 10 * MS, 35 * MS),
                ("copy.39.remat", "bf16[16,32,12288,128]", base + 12 * MS,
                 20 * MS),
                ("paged_attention.5", "bf16[32,32,1,128]", base + 33 * MS,
                 10 * MS)]
        names += ["jit(_decode)/while",
                  "jit(_decode)/while/body/closed_call/block.attn/kv_write/"
                  "scatter",
                  "jit(_decode)/while/body/closed_call/block.attn/attend/"
                  "paged_attention/pallas_call"]
        step = int(base // (50 * MS))
        for name, a, b, attrs in (
                ("serve.step", 0, 48, {"step": 40 + step}),
                ("serve.admit", 1, 4, {}),
                ("serve.admit.alloc", 2, 3, {"rid": 7}),
                ("serve.decode", 5, 47, {"lanes": 28}),
                ("serve.decode.build", 5, 9, {}),
                ("serve.decode.dispatch", 9, 10, {}),
                ("serve.decode.fetch", 10, 46, {}),
                ("serve.decode.bookkeep", 46, 47, {})):
            prog.append((name, base + a * MS, (b - a) * MS, attrs, "python"))
    host = [("window", 0.0, 100 * MS), ("step", 0.0, 49 * MS),
            ("step", 50 * MS, 49 * MS)]
    return spans.ProgramTrace(Trace({TPU: ops}, host), {TPU: names},
                              sorted(prog, key=lambda s: (s[1], -s[2])))


def test_span_self_time_by_hand():
    self_ms = {k: v / MS for k, v in spans.span_self_ns(hand_made()).items()}
    assert self_ms == pytest.approx({
        "serve.step": 2 * (48 - 3 - 42), "serve.admit": 2 * 2,
        "serve.admit.alloc": 2 * 1, "serve.decode": 2 * (42 - 4 - 1 - 36 - 1),
        "serve.decode.build": 2 * 4, "serve.decode.dispatch": 2 * 1,
        "serve.decode.fetch": 2 * 36, "serve.decode.bookkeep": 2 * 1})
    obs = {"program": {"trace": hand_made()}, "counters": {"traced_steps": 2}}
    assert spans.span_self_ms({"span": "serve.decode.build",
                               "per": "traced_steps"}, obs) == \
        pytest.approx(4.0)
    assert spans.span_self_ms({"span": "serve.prefill", "per":
                               "traced_steps"}, obs) is None


def test_idle_goes_to_the_deepest_span_by_hand():
    pt = hand_made()
    idle = {k: v / MS for k, v in spans.idle_by_span(pt).items()}
    assert idle == pytest.approx({"serve.decode.build": 10.0,
                                  "serve.admit.alloc": 15.0,
                                  "serve.step": 5.0})
    s = spans.sums(pt)
    assert s["idle_s"] == pytest.approx(0.030)
    assert s["idle_by_span_s"] == pytest.approx(s["idle_s"])
    assert s["idle_between_spans_s"] == 0.0
    # shared out by overlap. 0..10: the step itself 0..1 and 4..5, admit 2
    # beside its alloc 1, build 4, dispatch 1. 45..60: fetch 1, bookkeep 1,
    # step 0 itself 1, nothing open 48..50, then step 1 as in 0..10.
    # 95..100: fetch 1, bookkeep 1, the step itself 1, nothing open 2
    over = {k: v / MS for k, v in spans.idle_by_overlap(pt).items()}
    assert over == pytest.approx({
        "serve.step": 2 + 1 + 2 + 1, "serve.admit": 2 + 2,
        "serve.admit.alloc": 1 + 1, "serve.decode.build": 4 + 4,
        "serve.decode.dispatch": 1 + 1, "serve.decode.fetch": 1 + 1,
        "serve.decode.bookkeep": 1 + 1, "between_spans": 2 + 2})
    assert sum(over.values()) == pytest.approx(30.0)
    # the benchmark's own reader, on its own span, sees one label only
    assert dict(reduce.idle_gaps(pt.trace)) == pytest.approx({"step": 0.030})


def test_device_time_by_scope_by_hand():
    pt = hand_made()
    by = {k: v / MS for k, v in spans.device_ns_by_scope(pt).items()}
    assert by == pytest.approx({"block.attn.kv_write": 40.0,
                                "block.attn.attend": 20.0, "unscoped": 10.0})
    s = spans.sums(pt)
    assert s["by_scope_s"] == pytest.approx(s["busy_s"]) == \
        pytest.approx(0.070)
    assert s["unscoped_s"] == pytest.approx(0.010)
    top = spans.ops_by_scope(pt)
    assert top[0] == ["copy.39.remat bf16[16,32,12288,128]",
                      "block.attn.kv_write", pytest.approx(0.040)]
    obs = {"program": {"trace": pt}, "counters": {"traced_steps": 2}}
    assert spans.scope_device_ms({"scope": "block.attn.kv_write",
                                  "per": "traced_steps"}, obs) == \
        pytest.approx(20.0)
    assert spans.scope_device_ms({"phase": "forward", "per":
                                  "traced_steps"}, obs) == pytest.approx(30.0)
    assert spans.scope_device_ms({"phase": "backward", "per":
                                  "traced_steps"}, obs) is None


def test_phase_of_a_scope_path():
    assert [spans.phase_of(s) for s in (
        "block.attn.qkv", "backward:block.mlp", "recompute:block.attn",
        "optimizer", "grad_accum", "zero.scatter", "unscoped",
        "backward:unscoped", "no_op_name", "layers",
        "backward:layers")] == [
        "forward", "backward", "recompute", "optimizer", "accumulate",
        "accumulate", "unscoped", "backward", "no_op_name", "forward",
        "backward"]
    assert spans.scope_of("") == "no_op_name"
    assert spans.scope_of("jit(f)/jit(clip)/min") == "unscoped"
    assert spans.scope_of("jit(f)/transpose(jvp())/mul") == \
        "backward:unscoped"


def ring_of(steps):
    """A serve ring: ``steps`` is a list of (step number, counter gains,
    events inside); 10 ms a step."""
    ring, t = [], 0
    for n, gains, events in steps:
        for name, attrs in events:
            ring.append((name, "serve.step", t + 1, t + 1, attrs))
        ring.append(("serve.step", None, t, t + int(10 * MS),
                     {"step": n, "d": gains}))
        t += int(12 * MS)
    return ring


def test_window_steps_counters_and_requests_by_hand():
    pt = hand_made()                                   # traced steps 40, 41
    adm = lambda rid, arr, ts: ("serve.req.admitted",
                                {"rid": rid, "arrival_ts": arr, "ts": ts})
    first = lambda rid, ts: ("serve.req.first_token", {"rid": rid, "ts": ts})
    ring = ring_of([
        (41, {"lane_sum": 28}, [adm(1, 0.0, 0.1)]),    # traced: not counted
        (42, {"lane_sum": 27, "steps_with_queue": 1,
              "admit_blocked.prefilling": 1, "kv.held_blocks_sum": 300,
              "kv.blocks_reserved_sum": 10, "kv.tokens_written_sum": 200},
         [adm(2, 1.000, 1.200), first(1, 1.25)]),
        (43, {"lane_sum": 28, "steps_with_queue": 1,
              "kv.held_blocks_sum": 340, "kv.blocks_reserved_sum": 10,
              "kv.tokens_written_sum": 280},
         [adm(3, 1.100, 1.400), first(2, 1.450)]),
        (44, {"lane_sum": 28, "kv.held_blocks_sum": 320,
              "kv.blocks_reserved_sum": 10, "kv.tokens_written_sum": 320},
         [first(3, 2.000)]),
        (45, {"lane_sum": 5}, [adm(4, 3.0, 3.1), first(4, 3.2)])])  # drain
    obs = {"clocks": {"decode_step": [0.0101, 0.0099],
                      "prefill_step": [0.0100], "ttft": [1.0] * 9},
           "counters": {"usable_blocks": 383, "traced_steps": 2},
           "program": {"kind": "serve", "trace": pt, "ring": ring,
                       "serving": {"block_size": 32, "max_batch": 32}}}
    assert [s["n"] for s in spans.window_steps(obs)] == [42, 43, 44]
    assert spans.window_counter(obs, "lane_sum") == 83
    reqs = spans.window_requests(obs)
    assert [(r["arrival"], r["admitted"], r["first_token"]) for r in reqs] \
        == [(1.000, 1.200, 1.450), (1.100, 1.400, 2.000)]
    for r in reqs:                      # the three parts add up exactly
        assert (r["admitted"] - r["arrival"]) + \
            (r["first_token"] - r["admitted"]) == \
            r["first_token"] - r["arrival"]
        assert r["step_end"] >= 0
    q = lambda a, b, q: spans.request_quantile({"from": a, "to": b, "q": q},
                                               obs)
    assert q("arrival", "admitted", 0.5) == pytest.approx(250.0)
    assert q("admitted", "first_token", 0.5) == pytest.approx(425.0)
    m = spans.span_metrics("serve", obs)
    assert m["serve_admit_blocked_pct.prefilling"]["value"] == \
        pytest.approx(50.0)
    assert m["serve_admit_blocked_pct.no_lane"]["value"] == 0.0
    assert m["kv_reserved_unused_pct"]["value"] == \
        pytest.approx(100 * (1 - 800 / (30 * 32)))
    assert m["compiles_in_window"]["value"] == 0.0
    assert m["serve_host_self_ms_per_step.serve.decode.build"]["value"] == \
        pytest.approx(4.0)
    assert m["device_idle_ms_per_step.serve.admit.alloc"]["value"] == \
        pytest.approx(7.5)
    assert m["device_ms_per_step.block.attn.kv_write"]["value"] == \
        pytest.approx(20.0)
    outside = {"kv_pool_mean_used_pct": {"value": 100 * 320 / 383},
               "kv_pool_peak_used_pct": {"value": 100 * 340 / 383},
               "serve_lane_occupancy_pct": {"value": 100 * 83 / 96}}
    agree = spans.agreement("serve", obs, outside)
    assert set(agree) == {"median_step_ms", *outside}
    for a in agree.values():
        assert abs(a["inside"] - a["outside"]) <= a["tolerance"]


# ------------------------------------------------------- the xplane decoder


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def test_event_op_names_reads_tf_op_from_the_event_metadata(tmp_path):
    stat_meta = lambda i, name: _ld(5, _vi(1, i) + _ld(
        2, _vi(1, i) + _ld(2, name.encode())))
    event_meta = lambda i, name, stats: _ld(4, _vi(1, i) + _ld(
        2, _vi(1, i) + _ld(2, name.encode()) + b"".join(stats)))
    plane = (_vi(1, 3) + _ld(2, b"/device:TPU:0")
             + stat_meta(7, "tf_op") + stat_meta(8, "hlo_category")
             + stat_meta(9, "jit(f)/block.mlp/dot_general:")
             + event_meta(1, "%copy.39.remat = bf16[4]{0} copy(%x)", [
                 _ld(5, _vi(1, 8) + _ld(5, b"data formatting")),
                 _ld(5, _vi(1, 7) + _ld(
                     5, b"jit(_decode)/block.attn/kv_write/scatter:"))])
             + event_meta(2, "%fusion.2 = f32[] fusion(%y)", [
                 _ld(5, _vi(1, 7) + _vi(7, 9))])      # a ref_value
             + event_meta(3, "%while.1 = () while(%t)", []))
    host = _vi(1, 4) + _ld(2, b"/host:CPU") + stat_meta(7, "tf_op")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_ld(1, plane) + _ld(1, host))
    assert spans.event_op_names(str(path)) == {"/device:TPU:0": {
        "%copy.39.remat = bf16[4]{0} copy(%x)":
            "jit(_decode)/block.attn/kv_write/scatter",
        "%fusion.2 = f32[] fusion(%y)": "jit(f)/block.mlp/dot_general"}}


# ------------------------------------------------ recorded on the chip


# ``between``: the most of the idle time that may lie under no span of the
# program. The serving loop is one call a step, so nearly none does; between
# two ``train_batch`` calls the caller (here the benchmark's driver, which
# makes the next batch and fetches the loss) holds a third to a half of it.
@pytest.mark.parametrize("name,kind,between,scopes", [
    ("trace_serve_spans.json.gz", "serve", 0.05,
     ["block.attn.kv_write", "block.attn.attend", "block.mlp", "head"]),
    ("trace_train_spans.json.gz", "train", 0.60,
     ["backward:block.attn", "backward:block.mlp", "backward:layers",
      "optimizer", "loss", "grad_accum"]),
])
def test_recorded_chip_trace(name, kind, between, scopes):
    pt = spans.ProgramTrace.from_json(
        json.load(gzip.open(os.path.join(DATA, name), "rt")))
    step_name, attr = spans.STEP[kind]
    steps = [s for s in spans.window_spans(pt) if s[0] == step_name]
    assert len(steps) >= 2
    numbers = [int(s[3][attr]) for s in steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))
    lo, hi = reduce.window_of(pt.trace)

    # self times: every span's self time is its duration less its direct
    # children's, worked out here span by span (quadratic, independent of
    # reduce.self_times), and they add up to the outermost spans' time
    inside = spans.window_spans(pt)
    brute = {}
    for i, (n, a, d, _, th) in enumerate(inside):
        kids = [(n2, a2, d2) for j, (n2, a2, d2, _, th2) in enumerate(inside)
                if j != i and th2 == th and a <= a2 and a2 + d2 <= a + d
                and (d2 < d or j > i)]
        direct = [k for k in kids if not any(
            k is not o and o[1] <= k[1] and k[1] + k[2] <= o[1] + o[2]
            and (o[2] > k[2]) for o in kids)]
        brute[n] = brute.get(n, 0.0) + d - sum(k[2] for k in direct)
    got = spans.span_self_ns(pt)
    assert set(got) == set(brute)
    for n in got:
        assert got[n] == pytest.approx(brute[n], abs=2.0), n

    # idle: every gap is put down to a span, and the sums add up
    s = spans.sums(pt)
    assert s["idle_by_span_s"] == pytest.approx(s["idle_s"], rel=1e-9)
    assert s["idle_between_spans_s"] <= between * s["idle_s"]
    over = spans.idle_by_overlap(pt)
    assert sum(over.values()) <= s["idle_s"] * 1e9 * (1 + 1e-9)
    assert sum(over.values()) >= 0.9 * s["idle_s"] * 1e9   # gaps over 50 us
    idle = spans.idle_by_span(pt)
    assert all(k == "between_spans" or k.startswith(kind + ".")
               for k in idle)
    # the benchmark's own reader still sees one span round the step
    outer = {"serve": "step", "train": "train_batch"}[kind]
    assert {k for k, _ in reduce.idle_gaps(pt.trace)} <= {
        outer, "between_spans", "make_batch", "client", "submit"}

    # scopes: they add up to the busy time, little is unscoped, and the
    # expected ones are there
    by = spans.device_ns_by_scope(pt)
    assert s["by_scope_s"] == pytest.approx(s["busy_s"], rel=0.02)
    assert s["unscoped_s"] < 0.10 * s["busy_s"]
    for scope in scopes:
        assert by.get(scope, 0) > 0, (scope, sorted(by))
    top = spans.ops_by_scope(pt)
    assert top[0][1] != "unscoped" and top[0][2] >= top[-1][2] > 0
    if kind == "train":
        phases = {p: sum(v for k, v in by.items() if spans.phase_of(k) == p)
                  for p in ("forward", "backward", "recompute", "optimizer")}
        assert all(v > 0 for v in phases.values())
        assert phases["backward"] > phases["forward"] > phases["optimizer"]


# ---------------------------------------------- inside against outside

TINY_SERVE = {
    "config": {"name": "mistral-tiny", "family": "mistral",
               "hidden_act": "silu", "hidden_size": 128,
               "intermediate_size": 256, "max_position_embeddings": 512,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
               "rope_theta": 10000.0, "sliding_window": 64,
               "tie_word_embeddings": False, "vocab_size": 512},
    "traffic": {"name": "tiny", "kind": "closed_loop", "clients": 4,
                "prompt_len": {"dist": "lognormal", "median": 24,
                               "sigma": 0.8, "min": 8, "max": 64},
                "output_len": {"dist": "lognormal", "median": 8,
                               "sigma": 0.6, "min": 4, "max": 16},
                "cycle": 8, "mix_seed": 1},
    "system": {"dtype": "float32",
               "serving": {"block_size": 16, "pool_blocks": 24,
                           "max_batch": 4, "max_blocks_per_seq": 8,
                           "prefill_chunk_tokens": 32, "prefix_cache": True},
               "check": {"prompt_lens": [12, 40], "new_tokens": 6}}}


@pytest.fixture(scope="module")
def rehearsed():
    """The serve driver at a tiny size on the CPU (``rehearse.py``'s
    sizes), traced, with the program's observations beside the driver's."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    real = harness.load_cell("serve-mistral-7b-l16-chat")
    cell = dataclasses.replace(
        real, config=TINY_SERVE["config"], traffic=TINY_SERVE["traffic"],
        system={**real.system, **TINY_SERVE["system"]})
    with tempfile.TemporaryDirectory(prefix="spans_rehearsal_") as tdir:
        out = harness.load_driver("serve").run(
            cell, seed=2 ** 31 + 11, seconds=4.0, trace=True,
            t0=time.perf_counter(), trace_dir=tdir, rehearsal=True)
        obs = spans.program_obs(cell, out, tdir)
    return cell, out, obs


def test_inside_agrees_with_outside_on_a_cpu_rehearsal(rehearsed):
    cell, out, obs = rehearsed
    steps = spans.window_steps(obs)
    width = len(obs["clocks"]["decode_step"]) + \
        len(obs["clocks"]["prefill_step"])
    assert len(steps) == width > 10
    outside = reduce.layer_metrics(harness.load_layer_metrics("serve"), obs)
    agree = spans.agreement("serve", obs, outside)
    assert set(agree) == {"median_step_ms", "kv_pool_mean_used_pct",
                          "kv_pool_peak_used_pct",
                          "serve_lane_occupancy_pct"}
    for name, a in agree.items():
        assert abs(a["inside"] - a["outside"]) <= a["tolerance"], (name, a)
    # the counters are the same sums the driver kept, to the block
    assert spans.window_counter(obs, "kv.held_blocks_sum") == \
        obs["counters"]["held_sum"]
    assert spans.window_counter(obs, "lane_sum") == \
        obs["counters"]["lane_sum"]
    assert spans.window_counter(obs, "steps") == width


def test_request_parts_and_the_result_line_on_a_cpu_rehearsal(rehearsed):
    cell, out, obs = rehearsed
    reqs = spans.window_requests(obs)
    assert len(reqs) >= 4
    for r in reqs:
        assert r["arrival"] <= r["admitted"] <= r["first_token"] \
            <= r["step_end"]
    # the driver stamps a first token after the step that made it returns:
    # its TTFT is the program's three parts, and a little of its own
    parts = sorted(r["step_end"] - r["arrival"] for r in reqs)
    assert min(obs["clocks"]["ttft"]) >= parts[0] - 1e-3
    line = json.loads(spans.finish(cell, out, obs))
    assert line["correct"] is True
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    names = set(line["metrics"])
    assert {"serve_queue_wait_p50_ms", "serve_queue_wait_p95_ms",
            "serve_admit_to_first_token_p50_ms", "kv_reserved_unused_pct",
            "prefix_hit_pct", "prefix_evict_scanned_per_step",
            "compiles_in_window", "serve_admit_blocked_pct.no_lane",
            "serve_admit_blocked_pct.no_blocks",
            "serve_admit_blocked_pct.prefilling",
            "serve_host_self_ms_per_step.serve.decode.build",
            "serve_host_self_ms_per_step.serve.admit.peek"} <= names
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 <= line["metrics"]["kv_reserved_unused_pct"]["value"] < 100
    assert set(line["breakdown"]) >= {"idle_gaps", "device_ops_by_scope",
                                      "sums", "agreement", "ttft_parts_ms"}
