"""Autotuner — searches ZeRO stage / micro-batch / remat configs for the
fastest training setup.

Capability parity with the reference's ``deepspeed/autotuning/autotuner.py``
(Autotuner.tune:421 — tuning spaces per ZeRO stage, micro-batch sweeps,
experiment scheduling, ranked results) + ``tuner/`` (grid / random /
model-based search). TPU reshape: an *experiment* is just a ds_config dict;
a *runner* executes it and returns metrics — in-process for tests and
notebook use (engine_runner), or a subprocess launching the user's training
script exactly like the reference's scheduler.py run_job (subprocess_runner;
the engine exits after ``end_profile_step`` writing its metric file when
DS_AUTOTUNING_METRIC_FILE is set).

Failed experiments (OOM, bad composition) score -inf and are kept in the
record with their error, matching the reference's error-result handling.
"""

from __future__ import annotations

import copy
import itertools
import json
import os
import random
import subprocess
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import logger

METRIC_FILE_ENV = "DS_AUTOTUNING_METRIC_FILE"


@dataclass
class Experiment:
    name: str
    config: Dict[str, Any]
    metrics: Optional[Dict[str, float]] = None
    error: Optional[str] = None
    overrides: Optional[Dict[str, Any]] = None
    slot: Optional[Dict[str, Any]] = None       # reservation it ran on

    @property
    def score(self) -> float:
        if self.metrics is None:
            return float("-inf")
        return self.metrics.get("throughput", float("-inf"))


def default_tuning_space(base_config: Dict[str, Any],
                         micro_batch_sizes: Optional[List[int]] = None,
                         zero_stages: Optional[List[int]] = None,
                         remat: Optional[List[bool]] = None) -> Dict[str, List]:
    """The reference's DEFAULT_TUNING_SPACE equivalent: per-ZeRO-stage spaces
    x micro-batch ladder x activation checkpointing."""
    mbs = micro_batch_sizes or [1, 2, 4, 8, 16]
    stages = zero_stages if zero_stages is not None else [0, 1, 2, 3]
    return {
        "train_micro_batch_size_per_gpu": mbs,
        "zero_optimization.stage": stages,
        "activation_checkpointing": remat if remat is not None else [False],
    }


def _set_path(cfg: Dict, dotted: str, value):
    parts = dotted.split(".")
    node = cfg
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


class GridSearchTuner:
    """reference: tuner/index_based_tuner.py GridSearchTuner."""

    def __init__(self, space: Dict[str, List]):
        keys = list(space)
        self._combos = [dict(zip(keys, vals))
                        for vals in itertools.product(*(space[k] for k in keys))]

    def __iter__(self):
        return iter(self._combos)


class RandomTuner:
    """reference: tuner/index_based_tuner.py RandomTuner."""

    def __init__(self, space: Dict[str, List], num_trials: int = 50,
                 seed: int = 0):
        combos = list(GridSearchTuner(space))
        rng = random.Random(seed)
        rng.shuffle(combos)
        self._combos = combos[:num_trials]

    def __iter__(self):
        return iter(self._combos)


class ModelBasedTuner:
    """Cost-model-guided search (reference: tuner/model_based_tuner.py).

    The reference fits an XGBoost regressor over config-features ->
    throughput and repeatedly runs the predicted-best untried config.
    xgboost is not in this image, so the surrogate is closed-form ridge
    regression over the same featurization (numeric keys as log2 values,
    categorical keys one-hot) — enough to capture the monotone-ish
    throughput surfaces of this space.

    Protocol with Autotuner: ``num_seed`` shuffled combos are measured
    first; after every experiment Autotuner calls ``observe(overrides,
    score)``; each subsequent ``__next__`` refits and yields the untried
    combo with the best predicted score, for ``num_trials`` total.
    """

    def __init__(self, space: Dict[str, List], num_trials: int = 16,
                 num_seed: int = 4, seed: int = 0, ridge: float = 1e-3):
        import numpy as np
        self._np = np
        self._keys = list(space)
        self._space = space
        combos = list(GridSearchTuner(space))
        random.Random(seed).shuffle(combos)
        self._combos = combos
        self.num_trials = min(num_trials, len(combos))
        self.num_seed = min(num_seed, self.num_trials)
        self._obs_x: List = []
        self._obs_y: List[float] = []
        self._tried: List[Dict] = []
        self.ridge = ridge

    def _feat(self, overrides: Dict[str, Any]):
        np = self._np
        feats = [1.0]                                   # bias
        for k in self._keys:
            vals = self._space[k]
            v = overrides[k]
            if all(isinstance(x, bool) for x in vals):
                feats.append(float(v))
            elif all(isinstance(x, (int, float)) and not isinstance(x, bool)
                     for x in vals):
                feats.append(float(np.log2(float(v) + 1.0)))
            else:                                       # categorical one-hot
                feats.extend(1.0 if v == x else 0.0 for x in vals)
        return np.asarray(feats, np.float64)

    def observe(self, overrides: Dict[str, Any], score: float) -> None:
        if score == float("-inf"):                      # failed run
            score = 0.0
        self._obs_x.append(self._feat(overrides))
        self._obs_y.append(float(score))

    def _predict_best(self) -> Optional[Dict[str, Any]]:
        np = self._np
        remaining = [c for c in self._combos if c not in self._tried]
        if not remaining:
            return None
        if len(self._obs_y) < 2:
            return remaining[0]
        X = np.stack(self._obs_x)
        y = np.asarray(self._obs_y)
        d = X.shape[1]
        w = np.linalg.solve(X.T @ X + self.ridge * np.eye(d), X.T @ y)
        preds = [float(self._feat(c) @ w) for c in remaining]
        return remaining[int(np.argmax(preds))]

    def __iter__(self):
        for i in range(self.num_trials):
            nxt = (self._combos[i] if i < self.num_seed
                   else self._predict_best())
            if nxt is None:
                return
            self._tried.append(nxt)
            yield nxt


class Autotuner:
    """Experiment loop: generate -> run -> rank (reference autotuner.py:421).

    runner(config_dict) -> metrics dict with at least {"throughput"} (samples
    per second); raise or return None for a failed experiment.
    """

    def __init__(self,
                 base_config: Dict[str, Any],
                 runner: Callable[..., Optional[Dict[str, float]]],
                 tuning_space: Optional[Dict[str, List]] = None,
                 tuner_type: str = "gridsearch",
                 num_trials: int = 50,
                 early_stopping: int = 0,
                 results_dir: Optional[str] = None,
                 resource_slots: Optional[List[Dict[str, Any]]] = None,
                 kill_factor: float = 3.0):
        self.base_config = base_config
        self.runner = runner
        self.space = tuning_space or default_tuning_space(base_config)
        if tuner_type in ("gridsearch", "grid"):
            self.tuner = GridSearchTuner(self.space)
        elif tuner_type == "random":
            self.tuner = RandomTuner(self.space, num_trials)
        elif tuner_type in ("model", "model_based"):
            self.tuner = ModelBasedTuner(self.space, num_trials)
        else:
            raise ValueError(f"unknown tuner_type '{tuner_type}' "
                             "(gridsearch | random | model)")
        self.early_stopping = early_stopping
        self.results_dir = results_dir
        self.experiments: List[Experiment] = []
        # parallel mode (reference scheduler.py:114,319): experiments run
        # concurrently over reserved slots, losing configs killed
        self.resource_slots = resource_slots
        self.kill_factor = kill_factor

    def _materialize(self, overrides: Dict[str, Any]) -> Dict[str, Any]:
        cfg = copy.deepcopy(self.base_config)
        for dotted, val in overrides.items():
            if dotted == "activation_checkpointing":
                _set_path(cfg, "activation_checkpointing.partition_activations",
                          bool(val))
            else:
                _set_path(cfg, dotted, val)
        # micro batch sweeps re-derive gas from the fixed global batch
        if "train_micro_batch_size_per_gpu" in overrides and \
                "train_batch_size" in cfg:
            cfg.pop("gradient_accumulation_steps", None)
        return cfg

    def _make_exp(self, overrides) -> Experiment:
        name = "exp_" + "_".join(
            f"{k.split('.')[-1]}{v}" for k, v in overrides.items())
        return Experiment(name=name, config=self._materialize(overrides),
                          overrides=overrides)

    def _record(self, exp: Experiment, best: float, since_best: int):
        """Shared per-experiment bookkeeping: observe, log, early-stop
        accounting. Returns (best, since_best)."""
        self.experiments.append(exp)
        if hasattr(self.tuner, "observe"):              # model-based feedback
            self.tuner.observe(exp.overrides, exp.score)
        logger.info("autotuning %s -> %s", exp.name,
                    exp.metrics or exp.error)
        if exp.score > best:
            return exp.score, 0
        return best, since_best + 1

    def _finish(self) -> List[Experiment]:
        self.experiments.sort(key=lambda e: e.score, reverse=True)
        if self.results_dir:
            self.write_results(self.results_dir)
        return self.experiments

    def tune(self) -> List[Experiment]:
        if self.resource_slots and len(self.resource_slots) > 1:
            return self._tune_parallel()
        best = float("-inf")
        since_best = 0
        for overrides in self.tuner:
            exp = self._make_exp(overrides)
            try:
                exp.metrics = self.runner(exp.config)
            except Exception as e:  # OOM / invalid composition: record + go on
                exp.error = f"{type(e).__name__}: {e}"
                logger.warning("autotuning experiment %s failed: %s",
                               exp.name, exp.error[:200])
            best, since_best = self._record(exp, best, since_best)
            if self.early_stopping and since_best >= self.early_stopping:
                logger.info("autotuning early stop after %d stale trials",
                            since_best)
                break
        return self._finish()

    def _tune_parallel(self) -> List[Experiment]:
        """Waved concurrency: up to n_slots candidates in flight, results
        fed back to the tuner between waves (model-based feedback still
        steers), stale-wave early stop preserved. The scheduler records
        runner failures into exp.error itself."""
        from .scheduler import ParallelScheduler
        sched = ParallelScheduler(self.runner, self.resource_slots,
                                  kill_factor=self.kill_factor)
        n = sched.rm.n_slots
        best = float("-inf")
        since_best = 0
        it = iter(self.tuner)
        done = False
        while not done:
            wave = []
            for _ in range(n):
                try:
                    wave.append(self._make_exp(next(it)))
                except StopIteration:
                    done = True
                    break
            if not wave:
                break
            sched.run_wave(wave)
            for exp in wave:
                best, since_best = self._record(exp, best, since_best)
            if self.early_stopping and since_best >= self.early_stopping:
                logger.info("autotuning early stop after %d stale trials",
                            since_best)
                break
        return self._finish()

    def best(self) -> Optional[Experiment]:
        return self.experiments[0] if self.experiments else None

    def write_results(self, results_dir: str) -> str:
        os.makedirs(results_dir, exist_ok=True)
        path = os.path.join(results_dir, "autotuning_results.json")
        with open(path, "w") as f:
            json.dump([{"name": e.name, "metrics": e.metrics,
                        "error": e.error, "config": e.config}
                       for e in self.experiments], f, indent=2)
        best = self.best()
        if best and best.metrics is not None:
            with open(os.path.join(results_dir, "best_config.json"), "w") as f:
                json.dump(best.config, f, indent=2)
        return path


# ---------------------------------------------------------------------------
# runners
# ---------------------------------------------------------------------------

def engine_runner(model_factory: Callable[[], Any],
                  batch_factory: Callable[[int], Any],
                  steps: int = 5,
                  warmup: int = 2) -> Callable[[Dict], Dict[str, float]]:
    """In-process experiment runner: builds a fresh engine per config, times
    `steps` train_batches. batch_factory(step) -> global batch."""
    import time

    import jax

    def run(config: Dict) -> Dict[str, float]:
        import deepspeed_tpu as ds
        cfg = copy.deepcopy(config)
        act = cfg.get("activation_checkpointing", {})
        model = model_factory()
        if act.get("partition_activations") and hasattr(model, "cfg"):
            import dataclasses
            model = type(model)(dataclasses.replace(model.cfg, remat=True))
        engine, *_ = ds.initialize(model=model, config=cfg,
                                   example_batch=batch_factory(0))
        for i in range(warmup):
            engine.train_batch(batch_factory(i))
        t0 = time.perf_counter()
        loss = None
        for i in range(steps):
            loss = engine.train_batch(batch_factory(warmup + i))["loss"]
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        bs = engine.train_batch_size
        return {"throughput": bs / dt, "step_time": dt,
                "train_batch_size": bs}

    return run


def subprocess_runner(cmd: List[str], exps_dir: str,
                      timeout: int = 1800) -> Callable[[Dict], Dict[str, float]]:
    """Script-mode runner (reference: scheduler.py run_job): writes the exp
    ds_config, launches `cmd + ['--deepspeed_config', path]`, and reads the
    metric file the engine writes at end_profile_step."""

    import itertools
    os.makedirs(exps_dir, exist_ok=True)
    # offset past any previous session's records in a reused exps_dir (the
    # per-run counter keeps concurrent threads collision-free)
    counter = itertools.count(
        sum(1 for f in os.listdir(exps_dir) if f.endswith("_config.json")))
    lock = threading.Lock()

    def run(config: Dict, slot: Optional[Dict] = None,
            deadline: Optional[Callable[[], Optional[float]]] = None
            ) -> Dict[str, float]:
        # one process per chip: sound only while THIS process stays off
        # JAX — a parent that holds the TPU leaves the experiment none
        from ..utils.chip_owner import refuse_children_on_held_tpu
        refuse_children_on_held_tpu("autotuner script runner", 1)
        with lock:
            n = next(counter)
        cfg_path = os.path.join(exps_dir, f"exp_{n}_config.json")
        metric_path = os.path.join(exps_dir, f"exp_{n}_metrics.json")
        cfg = copy.deepcopy(config)
        cfg.setdefault("autotuning", {})["enabled"] = True
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        if os.path.exists(metric_path):
            os.unlink(metric_path)      # a stale file from a previous
                                        # session must not score this run
        env = dict(os.environ, **{METRIC_FILE_ENV: metric_path})
        if slot:
            # pin the launch to its reservation (parallel scheduler):
            # device slots restrict the runtime's visible accelerators,
            # host slots carry explicit env
            if slot.get("devices"):
                dev = str(slot["devices"])
                env["DSTPU_SLOT_DEVICES"] = dev
                env["TPU_VISIBLE_CHIPS"] = dev
                env["TPU_VISIBLE_DEVICES"] = dev
            env.update(slot.get("env") or {})
        out_path = os.path.join(exps_dir, f"exp_{n}_output.log")
        out_f = open(out_path, "w")
        # file-backed output: PIPEs would need draining while we poll (a
        # chatty child fills the ~64KB pipe buffer and deadlocks)
        proc = subprocess.Popen(cmd + ["--deepspeed_config", cfg_path],
                                env=env, stdout=out_f,
                                stderr=subprocess.STDOUT, text=True)
        # poll so a losing config is killed as soon as its deadline expires
        # (a pre-launch budget would never bind for the first wave, when no
        # experiment has completed yet)
        import time as _time
        t0 = _time.monotonic()
        while True:
            try:
                proc.wait(timeout=2.0)
                break
            except subprocess.TimeoutExpired:
                pass
            rem = deadline() if deadline is not None else None
            if (rem is not None and rem <= 0) or                     _time.monotonic() - t0 > timeout:
                proc.kill()
                proc.wait()
                out_f.close()
                raise RuntimeError(
                    "experiment killed: losing config (exceeded the "
                    "scheduler deadline)" if rem is not None and rem <= 0
                    else f"experiment timed out after {timeout}s")
        out_f.close()
        if not os.path.exists(metric_path):
            with open(out_path) as f:
                tail = f.read()[-1000:]
            raise RuntimeError(
                f"experiment produced no metric file (rc={proc.returncode}): "
                f"{tail}")
        with open(metric_path) as f:
            return json.load(f)

    return run
