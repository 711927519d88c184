"""Real multi-PROCESS coverage: 2 OS processes, jax.distributed rendezvous.

Mirrors the reference's DistributedTest pattern (tests/unit/common.py:110 —
fork N ranks with a TCP store rendezvous, train, checkpoint).  Everything
else in this suite simulates multi-chip with 8 virtual devices in ONE
process; this test exercises the rank-bootstrap path those tests skip:
``deepspeed_tpu.init_distributed`` -> ``jax.distributed.initialize`` with
the DSTPU_* env contract the launcher sets (launcher/runner.py), a global
mesh spanning two processes, cross-process collectives in the train step,
and a rank-0 checkpoint write.
"""

import pathlib
import socket
import subprocess
import sys

import pytest

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)

WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

import numpy as np
import deepspeed_tpu as ds

ds.init_distributed()          # DSTPU_COORDINATOR_ADDRESS / _NUM_PROCESSES / _PROCESS_ID
rank = ds.comm.get_rank()
world = ds.comm.get_world_size()
assert world == 2, world
assert len(jax.devices()) == 2          # one local device per process, global view

sys.path.insert(0, os.path.join(os.environ["DSTPU_TEST_REPO"], "tests"))
from util import SimpleModel, random_batch

config = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 1},
    "seed": 11,
}
engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                           example_batch=random_batch(8))
assert engine.dp_world_size == 2
# correctness here is the rank bootstrap + cross-process collectives +
# sharded checkpointing, not convergence (batch 8 is noisy): finite losses,
# and both ranks must report IDENTICAL values (the psum really synced)
losses = [float(engine.train_batch(random_batch(8, seed=i))["loss"])
          for i in range(12)]
assert np.isfinite(losses).all(), losses

ckdir = os.environ["DSTPU_TEST_CKPT"]
engine.save_checkpoint(ckdir, tag="mp")
print(f"RANK{rank} OK last={losses[-1]:.4f}", flush=True)
"""


WORKER_TP_PP = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

import numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, causal_lm_loss
from deepspeed_tpu.models.pipeline import build_pipelined_model

ds.init_distributed()
rank = ds.comm.get_rank()
assert ds.comm.get_world_size() == 2
assert len(jax.devices()) == 4              # 2 virtual devices per process
assert len(jax.local_devices()) == 2

# leg 1: ZeRO-1 + TP=2 — the model axis spans the PROCESS boundary, so
# every qkv/mlp matmul's psum rides the gloo transport (the launcher
# contract has only ever carried dp=2 before this test)
model, cfg = build_model("gpt2-tiny", hidden_size=64, num_layers=2,
                         num_heads=4, vocab_size=256, max_seq_len=64,
                         attention_impl="reference")
config = {
    "train_batch_size": 4,
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 1},
    "tensor_parallel": {"tp_size": 2},
    "seed": 17,
}
batch = {"input_ids": np.random.default_rng(3).integers(0, 256, (4, 32))}
eng, *_ = ds.initialize(model=model, config=config,
                        loss_fn=causal_lm_loss, example_batch=batch,
                        sharding_rules=cfg.tp_rules())
tl = [float(eng.train_batch(batch)["loss"]) for _ in range(3)]
assert np.isfinite(tl).all(), tl

# leg 2: PP=2 (GPipe SPMD) x DP=2 — the ppermute stage boundary crosses
# processes
piped, pcfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=2,
                                    hidden_size=64, num_layers=2,
                                    num_heads=4, vocab_size=256,
                                    max_seq_len=64,
                                    attention_impl="reference")
pconfig = {
    "train_batch_size": 8,
    "train_micro_batch_size_per_gpu": 2,
    "gradient_accumulation_steps": 2,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 1},
    "pipeline": {"stages": 2},
    "seed": 17,
}
pbatch = {"input_ids": np.random.default_rng(4).integers(0, 256, (8, 32))}
peng, *_ = ds.initialize(model=piped, config=pconfig,
                         loss_fn=causal_lm_loss, example_batch=pbatch,
                         sharding_rules=piped.tp_rules())
pl = [float(peng.train_batch(pbatch)["loss"]) for _ in range(3)]
assert np.isfinite(pl).all(), pl

print(f"RANK{rank} OK tp={tl[-1]:.4f} pp={pl[-1]:.4f}", flush=True)
"""


WORKER_RANK_FAILPOINT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

import deepspeed_tpu as ds

ds.init_distributed()
rank = ds.comm.get_rank()
sys.path.insert(0, os.path.join(os.environ["DSTPU_TEST_REPO"], "tests"))
from util import SimpleModel, random_batch
from deepspeed_tpu.runtime import checkpointing as ck

config = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 1},
    "seed": 11,
}
engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                           example_batch=random_batch(8))
ckdir = os.environ["DSTPU_TEST_CKPT"]
engine.train_batch(random_batch(8, seed=0))
engine.save_checkpoint(ckdir)             # clean sharded save: both ranks ok
# non-zero ranks return from the save's allgather BEFORE rank 0 publishes
# `latest` — order the read behind the publish
ds.comm.barrier("after-save-1")
assert ck.get_latest_tag(ckdir) == "global_step1", ck.get_latest_tag(ckdir)

engine.train_batch(random_batch(8, seed=1))
# DSTPU_CHAOS (rank 1 only, skip=2) fails rank 1's shard writes HERE: the
# failure folds into the ok flag, every rank reaches the allgather, and
# `latest` must not advance onto the half-written tag
engine.save_checkpoint(ckdir)
ds.comm.barrier("after-save-2")
assert ck.get_latest_tag(ckdir) == "global_step1", ck.get_latest_tag(ckdir)

# no rank hung in the barrier AND the collectives still work after the
# failed save — the surviving-rank path is genuinely alive
loss = float(engine.train_batch(random_batch(8, seed=2))["loss"])
assert loss == loss, loss
print(f"RANK{rank} SURVIVED ok", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# tier-2 (round 8 budget): the 2-proc gloo category runs in tier2/chaos.sh;
# in-process multi-device engine training keeps gating tier-1
@pytest.mark.slow
def test_two_process_train_and_checkpoint(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    port = _free_port()
    ck = tmp_path / "ck"
    procs = []
    for pid in range(2):
        env = dict(**__import__("os").environ,
                   DSTPU_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2",
                   DSTPU_PROCESS_ID=str(pid),
                   DSTPU_TEST_REPO=REPO_ROOT,
                   DSTPU_TEST_CKPT=str(ck))
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out[-3000:]}"
        assert f"RANK{pid} OK" in out, out[-2000:]
    # both ranks computed the same loss (the collectives really synced)
    l0 = outs[0].split("last=")[1].split()[0]
    l1 = outs[1].split("last=")[1].split()[0]
    assert l0 == l1, (l0, l1)
    assert (ck / "mp").is_dir()

    # the 2-process job wrote SHARDED files (per-host pieces, no gather);
    # restore them here in the single-process 8-device suite — a
    # cross-process-count universal restore
    shard_files = list((ck / "mp").glob("model_states-shard*.npz"))
    assert len(shard_files) == 2, shard_files

    import deepspeed_tpu as ds
    from util import SimpleModel, random_batch
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "seed": 11,
    }
    engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                               example_batch=random_batch(8))
    engine.load_checkpoint(str(ck), tag="mp")
    assert int(engine.state.step) == 12
    m = engine.train_batch(random_batch(8, seed=100))
    assert float(m["loss"]) == float(m["loss"])   # finite, trains on

@pytest.mark.slow
def test_two_process_sharded_save_with_per_rank_failpoint(tmp_path):
    """ROADMAP gap (round-4): the REAL multi-host save path under a
    per-rank fault. Rank 1's shard writes fail mid-sharded-save (via
    DSTPU_CHAOS threaded into just that worker's env — the launcher now
    forwards DSTPU_* for exactly this); the PR-3 ok-flag/allgather path
    must keep every rank out of a hung barrier, leave `latest` on the
    previous tag, and quarantine the shared staging dir."""
    import os
    worker = tmp_path / "worker_failpoint.py"
    worker.write_text(WORKER_RANK_FAILPOINT)
    port = _free_port()
    ckdir = tmp_path / "ck"
    procs = []
    for pid in range(2):
        env = dict(os.environ,
                   DSTPU_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2",
                   DSTPU_PROCESS_ID=str(pid),
                   DSTPU_TEST_REPO=REPO_ROOT,
                   DSTPU_TEST_CKPT=str(ckdir))
        env.pop("JAX_PLATFORMS", None)
        env.pop("DSTPU_CHAOS", None)
        if pid == 1:
            # skip the 2 clean first-save shard files, then fail every
            # write of the second save — rank 0 stays fault-free
            env["DSTPU_CHAOS"] = "ckpt.write:raise:skip=2:times=100"
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out[-3000:]}"
        assert f"RANK{pid} SURVIVED ok" in out, out[-2000:]

    from deepspeed_tpu.runtime import checkpointing as ck
    assert ck.get_latest_tag(str(ckdir)) == "global_step1"
    assert ck.list_tags(str(ckdir)) == ["global_step1"]
    # the half-written tag was quarantined for forensics, not published
    assert any(n.startswith("global_step2") and
               n.endswith(ck.QUARANTINE_SUFFIX)
               for n in os.listdir(ckdir)), os.listdir(ckdir)


@pytest.mark.slow
def test_two_process_tp_and_pp(tmp_path):
    """TP=2 and PP=2 over two REAL OS processes x 4 global devices (2 local
    each): the reference runs its whole feature matrix under
    launcher-spawned per-device processes (launcher/launch.py:129); before
    this test the jax.distributed path had only ever carried dp=2."""
    worker = tmp_path / "worker_tp_pp.py"
    worker.write_text(WORKER_TP_PP)
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(**__import__("os").environ,
                   DSTPU_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2",
                   DSTPU_PROCESS_ID=str(pid),
                   DSTPU_TEST_REPO=REPO_ROOT)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=900)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out[-3000:]}"
        assert f"RANK{pid} OK" in out, out[-2000:]
    # both ranks must agree on both legs' losses (the collectives synced);
    # parse the tokens rather than the raw tail (stderr is merged, so
    # teardown log lines may follow the OK print)
    def tokens(out):
        return (out.split("tp=")[1].split()[0], out.split("pp=")[1].split()[0])
    assert tokens(outs[0]) == tokens(outs[1]), (outs[0][-200:], outs[1][-200:])


WORKER_SDC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

import deepspeed_tpu as ds

ds.init_distributed()
rank = ds.comm.get_rank()
assert ds.comm.get_world_size() == 2
assert len(jax.devices()) == 4          # dp=4: a real majority vote

sys.path.insert(0, os.path.join(os.environ["DSTPU_TEST_REPO"], "tests"))
from util import SimpleModel, random_batch
from deepspeed_tpu.runtime.sentinel import TrainingIntegrityError

config = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 0},      # replicated state: auditable
    "seed": 11,
    "steps_per_print": 1000,
    "integrity": {"audit_interval": 3},
}
engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                           example_batch=random_batch(8))
try:
    for i in range(6):
        engine.train_batch(random_batch(8, seed=i))
    print(f"RANK{rank} NO-DETECT", flush=True)
    sys.exit(1)
except TrainingIntegrityError as e:
    # mirror launch.py's rc mapping: the integrity contract is the rc
    print(f"RANK{rank} DETECTED {e}", flush=True)
    sys.exit(e.exit_code)
"""


@pytest.mark.slow
def test_two_process_sdc_bitflip_detected_and_attributed(tmp_path):
    """Acceptance (round 7): a silent bit-flip on ONE replica of a 2-proc
    x 2-device world is caught by the cross-replica audit within
    audit_interval steps, EVERY rank aborts with rc 118, and only the
    implicated rank's heartbeat record carries the SDC flag — in the
    operator's hostfile vocabulary, so the elastic agent can quarantine
    the right host."""
    worker = tmp_path / "worker_sdc.py"
    worker.write_text(WORKER_SDC)
    port = _free_port()
    hbdir = tmp_path / "hb"
    procs = []
    for pid in range(2):
        env = dict(**__import__("os").environ,
                   DSTPU_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2",
                   DSTPU_PROCESS_ID=str(pid),
                   DSTPU_TEST_REPO=REPO_ROOT,
                   DSTPU_HEARTBEAT_DIR=str(hbdir),
                   DSTPU_HEARTBEAT_HOST=f"w{pid}",
                   # keyed chaos: the flip lands on process 1 only
                   DSTPU_CHAOS="sentinel.sdc:flag:match=1")
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 118, \
            f"rank {pid} rc={p.returncode}:\n{out[-3000:]}"
        assert f"RANK{pid} DETECTED" in out, out[-2000:]
    from deepspeed_tpu.runtime import heartbeat as hb
    flagged = hb.flagged_ranks(str(hbdir))
    assert list(flagged) == [1], flagged       # only the implicated rank
    assert flagged[1]["host"] == "w1"
    assert "SDC" in flagged[1]["flags"]
