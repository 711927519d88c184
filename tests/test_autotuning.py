"""Autotuning tests: tuner enumeration, experiment ranking with failures,
in-process engine runner on the CPU mesh, and the script-mode metric hook.

Mirrors the reference's tests/unit/autotuning coverage of tuning-space
generation + the scheduler's result handling.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (Autotuner, GridSearchTuner, RandomTuner,
                                      engine_runner)
from deepspeed_tpu.autotuning.autotuner import default_tuning_space

from util import SimpleModel, random_batch


def test_grid_tuner_enumerates_product():
    space = {"a": [1, 2], "b.c": [10, 20, 30]}
    combos = list(GridSearchTuner(space))
    assert len(combos) == 6
    assert {"a": 1, "b.c": 30} in combos


def test_random_tuner_caps_trials():
    space = {"a": list(range(10)), "b": list(range(10))}
    assert len(list(RandomTuner(space, num_trials=7))) == 7


def test_autotuner_ranks_and_records_failures(tmp_path):
    calls = []

    def runner(cfg):
        mb = cfg["train_micro_batch_size_per_gpu"]
        calls.append(mb)
        if mb == 4:
            raise MemoryError("simulated OOM")
        return {"throughput": float(mb * 100)}

    base = {"train_batch_size": 64, "train_micro_batch_size_per_gpu": 1}
    tuner = Autotuner(base, runner,
                      tuning_space={"train_micro_batch_size_per_gpu": [1, 2, 4]},
                      results_dir=str(tmp_path))
    exps = tuner.tune()
    assert [e.name for e in exps][0].endswith("2")       # mb=2 wins
    failed = [e for e in exps if e.error]
    assert len(failed) == 1 and "OOM" in failed[0].error
    results = json.load(open(tmp_path / "autotuning_results.json"))
    assert len(results) == 3
    best = json.load(open(tmp_path / "best_config.json"))
    assert best["train_micro_batch_size_per_gpu"] == 2


# tier-2 (round 10 budget): fattest passing legs demoted per the standing
# guardrail — tier-1 crept past ~80% of the 870s budget once the comm-plan
# legs landed; cheaper cousins still gate tier-1
@pytest.mark.slow
def test_engine_runner_on_cpu_mesh(tmp_path):
    """End-to-end: grid over micro-batch x ZeRO stage with real engines;
    every experiment must produce a throughput."""
    base = {"train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    space = {"train_micro_batch_size_per_gpu": [1, 2],
             "zero_optimization.stage": [0, 1]}
    runner = engine_runner(lambda: SimpleModel(),
                           lambda i: random_batch(16, seed=i), steps=3,
                           warmup=1)
    tuner = Autotuner(base, runner, tuning_space=space,
                      results_dir=str(tmp_path))
    exps = tuner.tune()
    assert len(exps) == 4
    assert all(e.metrics is not None for e in exps), \
        [(e.name, e.error) for e in exps]
    assert exps[0].score >= exps[-1].score


def test_script_mode_metric_hook(tmp_path):
    """The engine must write its metric file and exit at end_profile_step
    when launched under the autotuner (reference: autotuning exit path)."""
    script = tmp_path / "train.py"
    script.write_text("""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import deepspeed_tpu as ds
from util import SimpleModel, random_batch
cfg = json.load(open(sys.argv[sys.argv.index("--deepspeed_config") + 1]))
engine, *_ = ds.initialize(model=SimpleModel(), config=cfg,
                           example_batch=random_batch(8))
for i in range(100):
    engine.train_batch(random_batch(8, seed=i))
raise SystemExit("engine did not exit at end_profile_step")
""".format(repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
           tests=os.path.dirname(os.path.abspath(__file__))))
    cfg_path = tmp_path / "base.json"
    cfg_path.write_text(json.dumps({
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "autotuning": {"enabled": True, "end_profile_step": 4},
    }))
    metric_path = tmp_path / "metrics.json"
    env = dict(os.environ, DS_AUTOTUNING_METRIC_FILE=str(metric_path))
    proc = subprocess.run(
        [sys.executable, str(script), "--deepspeed_config", str(cfg_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.load(open(metric_path))
    assert metrics["throughput"] > 0
    assert metrics["steps"] == 4


def test_model_based_tuner_finds_optimum():
    """The ridge-surrogate tuner (reference: tuner/model_based_tuner.py)
    finds the best config on a synthetic throughput surface while trying
    fewer configs than the full grid."""
    from deepspeed_tpu.autotuning import Autotuner, ModelBasedTuner

    space = {
        "train_micro_batch_size_per_gpu": [1, 2, 4, 8, 16, 32],
        "zero_optimization.stage": [0, 1, 2, 3],
        "activation_checkpointing": [False, True],
    }
    # throughput rises with micro batch, dips at stage 3, remat costs 10%
    def runner(cfg):
        mb = cfg["train_micro_batch_size_per_gpu"]
        stage = cfg["zero_optimization"]["stage"]
        remat = cfg.get("activation_checkpointing", {}).get(
            "partition_activations", False)
        thr = mb * (0.8 if stage == 3 else 1.0) * (0.9 if remat else 1.0)
        return {"throughput": thr}

    tuner = Autotuner({"train_batch_size": 64}, runner, tuning_space=space,
                      tuner_type="model", num_trials=14)
    exps = tuner.tune()
    assert len(exps) == 14 < 6 * 4 * 2            # fewer than the grid
    best = tuner.best()
    assert best.config["train_micro_batch_size_per_gpu"] == 32
    assert best.config["zero_optimization"]["stage"] != 3
    # the model guided later trials toward large micro batches: the best
    # config must have been found despite sampling < 30% of the grid
    assert best.score == 32.0


# -- parallel scheduler (round-3 Missing #5) ----------------------------------


def test_parallel_scheduler_runs_concurrently_with_reservations():
    """Experiments overlap in time (up to n_slots in flight) and no slot is
    ever double-booked — the reference scheduler.py reservation semantics."""
    import threading
    import time

    from deepspeed_tpu.autotuning.autotuner import Experiment
    from deepspeed_tpu.autotuning.scheduler import ParallelScheduler

    active = {"n": 0, "max": 0, "by_slot": set()}
    lock = threading.Lock()

    def runner(config, slot, deadline):
        with lock:
            key = slot["devices"]
            assert key not in active["by_slot"], "slot double-booked"
            active["by_slot"].add(key)
            active["n"] += 1
            active["max"] = max(active["max"], active["n"])
        time.sleep(0.2)
        with lock:
            active["by_slot"].discard(key)
            active["n"] -= 1
        return {"throughput": float(config["x"])}

    sched = ParallelScheduler(runner,
                              [{"devices": "0"}, {"devices": "1"}])
    exps = [Experiment(name=f"e{i}", config={"x": i}) for i in range(6)]
    t0 = time.perf_counter()
    sched.run_wave(exps)
    wall = time.perf_counter() - t0
    assert all(e.metrics is not None for e in exps)
    assert active["max"] == 2, active           # really concurrent
    assert wall < 6 * 0.2                       # faster than sequential
    assert {e.slot["devices"] for e in exps} == {"0", "1"}


def test_parallel_scheduler_kills_losing_configs():
    """Once a config completes, a still-running experiment past
    kill_factor x the best wall time sees its deadline expire (losing
    configs give their slot back instead of running out the clock)."""
    import time

    from deepspeed_tpu.autotuning.autotuner import Experiment
    from deepspeed_tpu.autotuning.scheduler import ParallelScheduler

    def runner(config, slot, deadline):
        if config["kind"] == "fast":
            time.sleep(0.1)
            return {"throughput": 100.0}
        # losing config: poll the deadline like a real runner would
        for _ in range(200):
            time.sleep(0.05)
            rem = deadline()
            if rem is not None and rem <= 0:
                raise RuntimeError("killed: losing config")
        return {"throughput": 1.0}

    sched = ParallelScheduler(runner, [{"devices": "0"}, {"devices": "1"}],
                              kill_factor=2.0, min_kill_time=0.3)
    exps = [Experiment(name="fast", config={"kind": "fast"}),
            Experiment(name="slow", config={"kind": "slow"})]
    t0 = time.perf_counter()
    sched.run_wave(exps)
    wall = time.perf_counter() - t0
    assert exps[0].metrics == {"throughput": 100.0}
    assert exps[1].error is not None and "killed" in exps[1].error
    assert wall < 3.0, wall                     # the slow one did NOT run out


def test_autotuner_parallel_mode_matches_sequential_ranking(tmp_path):
    """Autotuner with resource_slots produces the same best config as the
    sequential path, with experiments actually distributed over slots."""
    import time

    from deepspeed_tpu.autotuning.autotuner import Autotuner

    space = {"train_micro_batch_size_per_gpu": [1, 2, 4, 8]}
    base = {"train_batch_size": 64}

    def runner(config, slot=None, deadline=None):
        time.sleep(0.05)
        return {"throughput": float(config["train_micro_batch_size_per_gpu"])}

    at = Autotuner(base, runner, tuning_space=space,
                   resource_slots=[{"devices": "0"}, {"devices": "1"}],
                   results_dir=str(tmp_path))
    exps = at.tune()
    assert at.best().config["train_micro_batch_size_per_gpu"] == 8
    assert len(exps) == 4
    assert {e.slot["devices"] for e in exps} == {"0", "1"}
    assert (tmp_path / "best_config.json").exists()
