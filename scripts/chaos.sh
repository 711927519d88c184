#!/usr/bin/env bash
# Fault-injection suite, standalone: crash a real checkpoint save at every
# named failpoint (plus kill-mid-write and SIGTERM subprocess tests), prove
# resume, and drive the run-supervision matrices — fail-fast teardown,
# phase-aware watchdog (compile-hang stack-dump/rc-117), heartbeat-loss and
# heartbeat-silence detection (RunSupervisor + BackendSupervisor incl. the
# backend kill path), blackholed-host blacklisting with degraded-world
# elastic resume, connect retries, rc-114 end-to-end through dstpu
# --elastic, and the per-rank failpoint in the REAL 2-process sharded save.
# Round 7 adds the training-integrity matrices: chaos grad spike -> in-jit
# skip with loss parity, spike storm -> verified rollback + data
# fast-forward, post-rollback reproduction -> rc-118 abort, and the
# cross-replica SDC bit-flip -> detection + host attribution (single-proc
# 8-device vote and the REAL 2-process world).
# Round 11 adds the serving-fleet matrices (tests/test_fleet.py): replica
# kill mid-decode -> exactly-once requeue with token-exact outputs,
# replica hang -> heartbeat-silence detection + blacklist/parole,
# retry-budget exhaustion -> FAILED, requeue-crash -> orphan retry, and
# serve.oom under the fleet.
# Round 15 adds the straggler-defense matrices (tests/test_straggler.py +
# the test_fleet straggler legs): a run.slow-degraded rank self-flags over
# the shared heartbeat channel, aborts rc 117, is struck and blacklisted
# by DSElasticAgent with the degraded world resuming training; a
# serve.replica_slow-degraded replica is drained exactly-once token-exact
# and blacklisted on repeat.
# Round 17 adds the low-precision training leg (tests/test_low_precision.py):
# chaos grad spike on a sentinel-gated int8 fake-quant engine -> in-jit
# skip + loss parity with the uninjected low-precision twin — the
# guardrail the activation_quant experiment is gated on, fired under it.
# Round 12 adds the disaggregated-serving matrices (tests/test_disagg.py):
# replica kill at serve.chunk / serve.handoff / serve.handoff_drop ->
# every request completes token-exact or FAILED-within-retry-budget with
# the SHARED pool's refcount accounting balanced after recovery, plus
# handoff backpressure/deadline units and chunk-progress carry.
# Round 19 adds the traffic-shaping matrices (tests/test_autoscale.py):
# serve.scale_up crash -> slot rollback with the fleet unchanged,
# scale-down-during-kill -> death concludes `retired` with exactly-once
# token-exact requeue and no replacement, serve.preempt crash ->
# orphan-parked victim resumed token-exact even when its old replica
# dies in the same window, plus the overload-ladder shed/reject legs and
# the process-placement autoscale/preempt (slow) legs.
# Includes the `slow`-marked engine-in-child tests tier-1 skips.
# See docs/RESILIENCE.md for the failpoint catalog and exit-code contract.
#
#   scripts/chaos.sh              # full crash-safety + supervision suite
#   scripts/chaos.sh -k sigterm   # subset (pytest -k forwarded)
set -euo pipefail
cd "$(dirname "$0")/.."

# determinism: the suite arms its own failpoints; a stray env spec would
# fire inside arbitrary tests (tests/conftest.py also scrubs this)
unset DSTPU_CHAOS

exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_chaos.py \
    tests/test_sentinel.py \
    tests/test_supervisor.py \
    tests/test_heartbeat.py \
    tests/test_multinode_runner.py \
    tests/test_launcher_elastic.py \
    tests/test_fleet.py \
    tests/test_autoscale.py \
    tests/test_straggler.py \
    tests/test_disagg.py \
    tests/test_low_precision.py \
    tests/test_mpmd.py \
    "tests/test_multiprocess.py::test_two_process_sharded_save_with_per_rank_failpoint" \
    "tests/test_multiprocess.py::test_two_process_sdc_bitflip_detected_and_attributed" \
    -q -p no:cacheprovider "$@"
