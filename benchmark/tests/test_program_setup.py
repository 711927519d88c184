"""The seven metrics of PR 56 (``program_metrics/setup_*_s.json`` and
``serve_wait_share_pct.json``): data files over two reducers ``program.py``
already has, reading the counters the recorder gained (``compile.backend_us``
and ``.cache_load_us`` apart, ``compile.first_call_rest_us``,
``compile.saved_us``, ``*.step_us``, ``*.wait_us``). By hand, on a
hand-made ``obs["program"]``: a snapshot and a ring of six steps with their
``d``; and on what a parent commit's recorder hands over: nothing.

``test_program.py`` (not this PR's to edit) still counts thirteen files, four
and twelve a kind; with these it is twenty, ten and nineteen, held here."""
import os

import pytest

from benchmark import program, spans
from benchmark.trace import Trace

SETUP = {"setup_backend_compile_s": ["compile.backend_us"],
         "setup_cache_load_s": ["compile.cache_load_us"],
         "setup_first_call_rest_s": ["compile.first_call_rest_us"],
         "setup_compile_saved_s": ["compile.saved_us"],
         "setup_steps_s": ["serve.step_us", "train.step_us"],
         "setup_device_wait_s": ["serve.wait_us", "train.wait_us"]}
NEW = sorted(SETUP) + ["serve_wait_share_pct"]


def files(kind: str) -> dict:
    return {m["name"]: m for m in program.load_metrics(kind)}


def test_the_new_files_load_and_name_a_reducer_the_program_reader_has():
    serve, train = files("serve"), files("train")
    assert len(os.listdir(program.METRICS_DIR)) == 13 + 7
    assert len(serve) == 12 + 7 and len(train) == 4 + 6
    assert set(NEW) <= set(serve) and set(SETUP) <= set(train)
    assert "serve_wait_share_pct" not in train
    shape = set(files("serve")["setup_trace_lower_s"])
    for name in NEW:
        m = serve[name]
        assert set(m) == shape and m["reducer"] in program.REDUCERS
        assert m["source"] == "program_counter"
    for name, counters in SETUP.items():
        m = serve[name]
        assert m == train[name]
        assert (m["reducer"], m["args"]) == (
            "program_setup_counter_s", {"counters": counters})
        assert (m["layer"], m["moves"], m["unit"], m["better"]) == (
            "entry points", "setup_s", "s", "lower")
    share = serve["serve_wait_share_pct"]
    assert (share["reducer"], share["layer"], share["moves"],
            share["unit"], share["better"]) == (
        "program_window_ratio", "serving loop", "serve_tokens_per_s", "%",
        "higher")
    assert share["args"] == {"num": "serve.wait_us", "den": "serve.step_us",
                             "scale": 100.0}


def hand_made(kind: str = "serve", recorder_of_today: bool = True) -> dict:
    """Six steps. Steps 0 and 1 are set-up (step 0 compiled: 2 s of backend,
    0.25 s of cache load whose entry says 3 s, 0.5 s of first-call rest);
    steps 2 and 3 are the traced window; two step clocks put the window at
    steps 4 and 5. The constructor compiled for 1 s (no step's gain). A
    parent commit's recorder counts ``steps`` and ``compiles`` only."""
    step, attr = spans.STEP[kind]
    gains = [
        {"steps": 1, "compiles": 2, f"{kind}.step_us": 5_000_000,
         f"{kind}.wait_us": 1_000_000, "compile.backend_us": 2_000_000,
         "compile.cache_load_us": 250_000, "compile.saved_us": 3_000_000,
         "compile.first_call_rest_us": 500_000},
        {"steps": 1, f"{kind}.step_us": 1_000_000,
         f"{kind}.wait_us": 700_000},
        {"steps": 1, f"{kind}.step_us": 20_000, f"{kind}.wait_us": 19_000},
        {"steps": 1, f"{kind}.step_us": 20_000, f"{kind}.wait_us": 19_000},
        {"steps": 1, f"{kind}.step_us": 30_000, f"{kind}.wait_us": 27_000},
        {"steps": 1, f"{kind}.step_us": 10_000, f"{kind}.wait_us": 3_000}]
    total = {"compile.backend_us": 1_000_000, f"{kind}.init_us": 4_000_000}
    for d in gains:
        for k, v in d.items():
            total[k] = total.get(k, 0) + v
    if not recorder_of_today:
        keep = ("steps", "compiles")
        gains = [{k: v for k, v in d.items() if k in keep} for d in gains]
        total = {k: v for k, v in total.items() if k in keep}
    ring = [(step, None, 10 * n, 10 * n + 5, {attr: n, "d": d})
            for n, d in enumerate(gains)]
    pt = spans.ProgramTrace(Trace({}, [("window", 100.0, 100.0)]), {}, [
        (step, 110.0, 10.0, {attr: 2}, "t"),
        (step, 130.0, 10.0, {attr: 3}, "t")])
    prog = {"kind": kind, "ring": ring, "trace": pt, "serving": {},
            "snapshot": {"counters": total, "ring_dropped": 0}}
    return {"program": prog, "clocks": {"decode_step": [0.03, 0.01]},
            "counters": {"traced_steps": 2}}


WANT = {"setup_backend_compile_s": 1.0 + 2.0,      # the constructor's too
        "setup_cache_load_s": 0.25,
        "setup_first_call_rest_s": 0.5,
        "setup_compile_saved_s": 3.0,
        "setup_steps_s": 5.0 + 1.0,                # not the window's 0.08
        "setup_device_wait_s": 1.0 + 0.7}


@pytest.mark.parametrize("kind", ["serve", "train"])
@pytest.mark.parametrize("name", sorted(SETUP))
def test_a_setup_metric_is_its_counter_at_the_windows_first_step(name, kind):
    m = files(kind)[name]
    obs = hand_made(kind)
    got = program.REDUCERS[m["reducer"]](m["args"], obs)
    assert got == pytest.approx(WANT[name])
    # the two that were one until now still add up to PR 38's metric
    both = files(kind)["setup_compile_or_load_s"]
    assert program.REDUCERS[both["reducer"]](both["args"], obs) == \
        pytest.approx(WANT["setup_backend_compile_s"]
                      + WANT["setup_cache_load_s"])


def test_the_wait_share_is_over_the_windows_steps_alone():
    m = files("serve")["serve_wait_share_pct"]
    got = program.REDUCERS[m["reducer"]](m["args"], hand_made())
    # steps 4 and 5: 27 + 3 ms waited of 30 + 10 ms
    assert got == pytest.approx(100.0 * 30_000 / 40_000)
    by_hand = program.metrics("serve", hand_made())
    assert by_hand["serve_wait_share_pct"] == {"value": got, "unit": "%"}
    assert {k: v["value"] for k, v in by_hand.items() if k in WANT} == \
        pytest.approx(WANT)


@pytest.mark.parametrize("name,kind", [(n, "serve") for n in NEW]
                         + [(n, "train") for n in sorted(SETUP)])
def test_a_parent_commits_recorder_gives_none_not_an_exception(name, kind,
                                                               capsys):
    m = files(kind)[name]
    reducer = program.REDUCERS[m["reducer"]]
    obs = hand_made(kind, recorder_of_today=False)
    assert reducer(m["args"], obs) is None
    assert name not in program.metrics(kind, obs)
    # nor where the run left nothing at all, or the ring lost the step
    assert reducer(m["args"], {"clocks": {}, "counters": {}}) is None
    lost = hand_made(kind)
    lost["program"]["ring"] = lost["program"]["ring"][-1:]
    assert reducer(m["args"], lost) in (None, pytest.approx(30.0))
    assert all(line.startswith("[bench] program: ")
               for line in capsys.readouterr().out.splitlines())
