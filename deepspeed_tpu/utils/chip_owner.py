"""One process per chip.

A TPU chip belongs to one process at a time, and a process that has touched
JAX on a TPU holds every chip it can see until it exits. A child it then
starts that needs a chip fails or hangs at backend start-up. The paths that
start such children (``serving.fleet.placement: "process"``, the MPMD stage
supervisor, the autotuner's script runner) ask here FIRST and fail with the
reason, rather than waiting out a warm-up timeout. Assigning a device to
each child is a feature this repo does not have yet.
"""

from __future__ import annotations

import sys


def parent_holds_tpu() -> bool:
    """Has THIS process initialised JAX on a TPU? (Never initialises it.)"""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() == "tpu"


def refuse_children_on_held_tpu(what: str, n_children: int) -> None:
    """Raise when ``what`` is about to start ``n_children`` processes that
    need a chip while this process holds the TPU."""
    if n_children > 0 and parent_holds_tpu():
        import jax
        raise RuntimeError(
            f"{what}: this process has already touched JAX and holds all "
            f"{len(jax.devices())} TPU chip(s) it can see, so none is free "
            f"for the {n_children} child process(es) that need one — a chip "
            "belongs to one process at a time, and the children would fail "
            "or hang at start-up. Run the replicas/stages in ONE process "
            "(thread placement; one process can drive every chip of a "
            "host), or start this parent without touching JAX "
            "(JAX_PLATFORMS=cpu for the parent).")
