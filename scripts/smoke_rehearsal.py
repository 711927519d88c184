#!/usr/bin/env python3
"""CPU rehearsal of chip_smoke.py's phases at a tiny size (no chip time).

    JAX_PLATFORMS=cpu python scripts/smoke_rehearsal.py              # train + serve
    JAX_PLATFORMS=cpu python scripts/smoke_rehearsal.py --multichip  # 4 virtual devices

Drives chip_smoke's OWN phase functions with the sizes below. Everything
that makes this a rehearsal lives here, so chip_smoke.py holds one set of
sizes and no check that can be turned off:

* ``TINY`` — the sizes;
* the serving engines run their Pallas kernels interpreted (the train phase
  rides the jnp attention: the CPU has no Mosaic);
* a check about the chip's compiled program or its memory counters cannot
  hold on the CPU: it is printed as ``chip`` and left to the chip run. Every
  other check fails the rehearsal as it fails the smoke.

It finds wrong paths, arguments and control flow — nothing else: it prints
no ``ok`` line and no number it prints is a device metric.
"""

import os
import sys
import tempfile

if "--multichip" in sys.argv[1:]:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import chip_smoke as cs  # noqa: E402
from deepspeed_tpu.inference.engine import InferenceEngine  # noqa: E402

TINY = cs.Sizes(preset="gpt2-tiny",
                model_kw=(("hidden_size", 128), ("num_layers", 2),
                          ("num_heads", 2), ("vocab_size", 512)),
                seq=128, rows=4, rows_multichip=8, steps=4, lr=1e-2,
                block_size=32, pool_blocks=24, max_batch=4,
                prompts=((64, True), (32, False), (96, True), (64, False)),
                new_tokens=(6, 4, 6, 4), int8_prompts=(32, 64),
                int8_new_tokens=4)

#: words of the checks only the chip can pass: Mosaic custom calls and
#: reduce-scatter in a COMPILED program, the allocator's PEAK counters
CHIP_ONLY = ("compiled", "peak")


def rehearse() -> None:
    """Point chip_smoke's module-level hooks at the CPU."""
    smoke_check, serve = cs.check, InferenceEngine.serve

    def check(ok: bool, what: str) -> None:
        if not ok and any(word in what for word in CHIP_ONLY):
            print("  chip " + what + "  [left to the chip run]", flush=True)
            return
        smoke_check(ok, what)

    cs.check = check
    cs.peak_bytes = lambda dev: 0       # the CPU client keeps no such counter
    InferenceEngine.serve = lambda self, **kw: serve(self, interpret=True,
                                                     **kw)


def main() -> int:
    if jax.default_backend() != "cpu":
        print("smoke_rehearsal: run with JAX_PLATFORMS=cpu (the chip run is "
              "`python chip_smoke.py`)", file=sys.stderr)
        return 2
    rehearse()
    watch = cs.CompileWatch()
    with tempfile.TemporaryDirectory(prefix="smoke_rehearsal_") as workdir:
        if "--multichip" in sys.argv[1:]:
            cs.multichip_phase(TINY, workdir, watch)
        else:
            engine, _ = cs.train_phase(TINY, workdir, watch, rows=TINY.rows)
            params = engine.state.params
            engine.state = engine.state.replace(params=None)
            cs.free_engine(engine)
            cs.serve_phase(TINY, workdir, params, watch)
    print("smoke_rehearsal: phases passed on the CPU at the TINY size "
          "(a rehearsal, not a result)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
