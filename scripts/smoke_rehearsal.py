#!/usr/bin/env python3
"""CPU rehearsal of chip_smoke.py's phases at a tiny size (no chip time).

    JAX_PLATFORMS=cpu python scripts/smoke_rehearsal.py              # train + serve
    JAX_PLATFORMS=cpu python scripts/smoke_rehearsal.py --multichip  # 4 virtual devices

Drives the SAME phase functions with ``chip_smoke.TINY``: the serving
kernels run in Pallas interpret mode, the train phase rides the jnp
attention (the CPU has no Mosaic), and the kernel-presence checks are off.
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


def main() -> int:
    if jax.default_backend() != "cpu":
        print("smoke_rehearsal: run with JAX_PLATFORMS=cpu (the chip run is "
              "`python chip_smoke.py`)", file=sys.stderr)
        return 2
    watch = cs.CompileWatch()
    with tempfile.TemporaryDirectory(prefix="smoke_rehearsal_") as workdir:
        if "--multichip" in sys.argv[1:]:
            cs.multichip_phase(cs.TINY, workdir, watch, expect_kernels=False)
        else:
            engine, _ = cs.train_phase(cs.TINY, workdir, watch,
                                       rows=cs.TINY.rows,
                                       expect_kernels=False)
            params = engine.state.params
            engine.state = engine.state.replace(params=None)
            cs.free_engine(engine)
            cs.serve_phase(cs.TINY, workdir, params, watch, interpret=True,
                           expect_kernels=False)
    print("smoke_rehearsal: phases passed on the CPU at the TINY size "
          "(a rehearsal, not a result)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
