"""The data-driven harness: cells, configurations and per-layer metrics are
files; ``BENCHMARK.json`` agrees with them; the result line has its keys."""
import json
import os
import shutil

import pytest

from benchmark import harness, reduce

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


class FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_result_line_keys():
    line = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        devices=[FakeDevice()], memory_peak=123))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123}
    traced = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0, metrics={},
        devices=[FakeDevice()], memory_peak=1, busy_s=0.5, window_s=1.0,
        breakdown={"device_ops": [], "idle_gaps": []}))
    assert set(traced) == set(line) | {"breakdown"}
    assert traced["device"]["busy_s"] == 0.5
    assert traced["device"]["window_s"] == 1.0


def test_benchmark_json_agrees_with_the_files():
    assert BENCH["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        f = harness.load_json(os.path.join(ROOT, c["file"]))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    kinds = {}
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell.config["name"], cell.traffic["name"], cell.chips,
                cell.why) == (w["config"], w["traffic"], w["chips"], w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        kinds.setdefault(cell.kind, set()).add(w["name"])
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    on_disk = {m["name"]: m for k, cells in kinds.items() for c in cells
               for m in harness.load_layer_metrics(k, cell=c)}
    assert set(listed) == set(on_disk)
    for name, m in on_disk.items():
        b = listed[name]
        assert {k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == \
            {k: b[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert b["moves"] in e2e and m["reducer"] in reduce.REDUCERS
        # a metric is held to the cells it names, or to every cell of its
        # kinds; every one of them reports the end-to-end metric it moves
        cells = set(m["workloads"]) if "workloads" in m else \
            set().union(*(kinds[k] for k in m["kinds"] if k in kinds))
        assert set(b["workloads"]) == cells <= set().union(*kinds.values())
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == b["moves"])
        assert cells <= set(moved.get("workloads", cells))
        for k, named in kinds.items():
            for c in named:
                assert (name in [x["name"] for x in harness.load_layer_metrics(
                    k, cell=c)]) == (c in cells)


def test_peaks_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(KeyError, match="no peaks recorded"):
        harness.peaks_for("cpu")


def test_config_cell_and_metric_are_added_as_new_files_only(tmp_path):
    base = str(tmp_path / "benchmark")
    for d in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(harness.HERE, d), os.path.join(base, d))
    before = {d: set(os.listdir(os.path.join(base, d)))
              for d in ("configs", "traffic", "workloads", "layer_metrics")}

    def add(d, name, obj):
        with open(os.path.join(base, d, name + ".json"), "x") as f:
            json.dump(obj, f)

    # a configuration: the same family at another published size
    cfg = dict(harness.load_cell("train-gpt2-1.3b-z3").config,
               name="gpt2-2.7b", num_layers=32, hidden_size=2560,
               num_attention_heads=32, ffn_hidden_size=10240)
    add("configs", "gpt2-2.7b", cfg)
    # a cell on four chips: the one-chip cell's file with two keys changed
    w = harness.load_json(os.path.join(base, "workloads",
                                       "train-gpt2-1.3b-z3.json"))
    add("workloads", "train-gpt2-2.7b-z3-dp4",
        dict(w, name="train-gpt2-2.7b-z3-dp4", config="gpt2-2.7b", chips=4,
             why="ZeRO-3 over dp 4"))
    # a per-layer metric: one more scope of the trace
    add("layer_metrics", "loss_scan_ms_per_step",
        {"name": "loss_scan_ms_per_step", "unit": "ms", "better": "lower",
         "layer": "model", "source": "device_trace",
         "moves": "train_tokens_per_s_per_chip", "kinds": ["train"],
         "reducer": "scope_time",
         "args": {"match": ["chunk_nll"], "per": "traced_steps",
                  "scale": 1000.0}})

    # a per-layer metric of a mechanism only some cells have names them
    add("layer_metrics", "gmm_ms_per_step",
        {"name": "gmm_ms_per_step", "unit": "ms", "better": "lower",
         "layer": "paged forward and kernel", "source": "device_trace",
         "moves": "itl_p95_ms", "workloads": ["serve-olmoe-1b-7b-l8-gen"],
         "reducer": "scope_time",
         "args": {"match": ["gmm"], "per": "traced_steps", "scale": 1000.0}})
    for cell_name, there in (("serve-olmoe-1b-7b-l8-gen", True),
                             ("serve-mistral-7b-l16-chat", False),
                             (None, False)):
        got = [m["name"] for m in harness.load_layer_metrics(
            "serve", base, cell=cell_name)]
        assert ("gmm_ms_per_step" in got) == there
        assert "paged_kernel_ms_per_step" in got

    cell = harness.load_cell("train-gpt2-2.7b-z3-dp4", base=base)
    assert cell.chips == 4 and cell.kind == "train"
    assert cell.config["hidden_size"] == 2560
    fam = harness.load_family(cell.config["family"])
    assert fam.dims(cell.config)["head_dim"] == 80
    names = [m["name"] for m in harness.load_layer_metrics("train", base)]
    assert "loss_scan_ms_per_step" in names and "train_step_ms" in names
    assert "loss_scan_ms_per_step" not in [
        m["name"] for m in harness.load_layer_metrics("serve", base)]
    # nothing that was there changed
    for d, files in before.items():
        assert files < set(os.listdir(os.path.join(base, d))) or d == "traffic"
        for fn in files:
            assert open(os.path.join(base, d, fn)).read() == \
                open(os.path.join(harness.HERE, d, fn)).read()


def test_a_cell_file_with_four_chips_loads_and_a_wrong_one_does_not(tmp_path):
    base = str(tmp_path / "benchmark")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(os.path.join(harness.HERE, d), os.path.join(base, d))
    w = harness.load_json(os.path.join(base, "workloads",
                                       "train-gpt2-1.3b-z3.json"))
    for chips, ok in ((4, True), (2, False)):
        name = f"dp{chips}"
        with open(os.path.join(base, "workloads", name + ".json"), "w") as f:
            json.dump(dict(w, name=name, chips=chips), f)
        if ok:
            assert harness.load_cell(name, base=base).chips == 4
        else:
            with pytest.raises(ValueError, match="not 1 or 4"):
                harness.load_cell(name, base=base)


@pytest.mark.parametrize("keys", [{}, {"kinds": ["serve"],
                                       "workloads": ["serve-x"]}],
                         ids=["neither", "both"])
def test_a_metric_lists_kinds_or_workloads_and_not_both(tmp_path, keys):
    d = tmp_path / "benchmark" / "layer_metrics"
    d.mkdir(parents=True)
    (d / "m.json").write_text(json.dumps(dict({"name": "m"}, **keys)))
    with pytest.raises(ValueError, match="either kinds or workloads"):
        harness.load_layer_metrics("serve", str(tmp_path / "benchmark"),
                                   cell="serve-x")


def test_result_line_ends_in_the_numbers_compared():
    line = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0, metrics={},
        devices=[FakeDevice()], memory_peak=1,
        breakdown={"device_ops": [], "idle_gaps": []},
        compared={"served_logit_gap": {"value": 0.04, "limit": 0.15}}))
    assert list(line)[-1] == "compared"
    assert line["compared"]["served_logit_gap"] == {"value": 0.04,
                                                    "limit": 0.15}
