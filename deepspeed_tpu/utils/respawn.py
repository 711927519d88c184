"""Environment for a subprocess that must see an N-device virtual CPU mesh.

One shared recipe (used by __graft_entry__.dryrun_multichip): multi-device
CPU work runs in a child whose environment pins the CPU platform and the
virtual device count. Includes the raised CPU-collective rendezvous
timeouts — device threads timeshare the host cores, and arrival skew at a
collective can exceed the runtime's default 40s abort on big programs.
"""

from __future__ import annotations

import os
from typing import Dict


def clean_cpu_env(n_devices: int, base: Dict[str, str] = None
                  ) -> Dict[str, str]:
    """Environment for a subprocess that must see n_devices CPU devices."""
    env = dict(base if base is not None else os.environ)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    flags += f" --xla_force_host_platform_device_count={n_devices}"
    flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
              " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
    env["XLA_FLAGS"] = flags.strip()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    return env
