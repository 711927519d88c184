"""Catalog of every collective entry point the analyzer models.

Three tiers, matched on canonical dotted names (aliases resolve through
the per-module import map, so ``from jax import lax; lax.psum`` and
``jax.lax.psum`` are the same entry):

in-program collectives (``jax.lax``)
    Execute inside a compiled program over named mesh axes. Every
    participant along the axis must execute the same program: a rank
    that never dispatches it wedges the others in the matched collective.

host collectives (``jax.experimental.multihost_utils``)
    Block the calling *process* until every process arrives — the
    sharded-save barrier family. A rank-conditional path around one of
    these is the exact shape of the pre-PR-3 checkpoint hang.

package facade (``deepspeed_tpu.comm``)
    The project's own wrappers (comm/comm.py). Cataloged by dotted name
    so a single-file lint of a caller still knows ``comm.barrier`` is a
    collective even when comm.py itself is outside the lint run; on a
    full-package run the call graph ALSO reaches the ``lax`` calls in
    their bodies, and the two sources agree.
"""

from __future__ import annotations

import ast
from typing import FrozenSet, Optional, Union

#: Sentinel context: "runs under a mesh whose axis names are not
#: statically visible" (axis_names built from a variable, or shard_map
#: deriving axes from a ``mesh=`` object). Rules stay silent rather than
#: guess.
UNKNOWN = "<unknown-axes>"

# canonical name -> index of the axis-name argument (after the tensor)
LAX_COLLECTIVES = {
    "jax.lax.psum": 1,
    "jax.lax.pmean": 1,
    "jax.lax.pmax": 1,
    "jax.lax.pmin": 1,
    "jax.lax.psum_scatter": 1,
    "jax.lax.all_gather": 1,
    "jax.lax.all_to_all": 1,
    "jax.lax.ppermute": 1,
    "jax.lax.pshuffle": 1,
}

# axis-consuming but not communicating: validity checked (TPU012), never
# a divergence hazard by itself (TPU011/TPU013 ignore them)
LAX_AXIS_USERS = {
    "jax.lax.axis_index": 0,
    "jax.lax.axis_size": 0,
}

HOST_COLLECTIVES = {
    "jax.experimental.multihost_utils.sync_global_devices",
    "jax.experimental.multihost_utils.broadcast_one_to_all",
    "jax.experimental.multihost_utils.process_allgather",
    "jax.experimental.multihost_utils.assert_equal",
}

# deepspeed_tpu.comm facade: both the defining module's dotted path and
# the package re-export resolve here. Values: axis kwarg semantics like
# the lax table (None = no axis argument).
_FACADE_FNS = {
    "all_reduce": 1, "all_gather": 1, "reduce_scatter": 1,
    "all_to_all": 1, "broadcast": None, "ppermute": 2,
    "send_recv_next": 1, "send_recv_prev": 1, "barrier": None,
}
FACADE_COLLECTIVES = {}
for _name, _pos in _FACADE_FNS.items():
    FACADE_COLLECTIVES[f"deepspeed_tpu.comm.{_name}"] = _pos
    FACADE_COLLECTIVES[f"deepspeed_tpu.comm.comm.{_name}"] = _pos

#: Wrappers that establish a named-axis context for the callable they map
SHARD_WRAPPERS = {"jax.shard_map", "shard_map"}
PMAP_WRAPPERS = {"jax.pmap"}

#: Mesh constructors whose axis tuple declares axis names project-wide
MESH_CTORS = {"jax.sharding.Mesh", "Mesh", "jax.make_mesh",
              "jax.interpreters.pxla.Mesh",
              "jax.experimental.mesh_utils.Mesh"}

AXIS_KWARGS = ("axis_name", "axis")


def collective_kind(q: Optional[str]) -> Optional[str]:
    """'lax' / 'host' / 'facade' for a canonical dotted name, else None."""
    if not q:
        return None
    if q in LAX_COLLECTIVES:
        return "lax"
    if q in HOST_COLLECTIVES:
        return "host"
    if q in FACADE_COLLECTIVES:
        return "facade"
    return None


def short_name(q: str) -> str:
    """Display name: last two components ('lax.psum', 'comm.barrier')."""
    parts = q.split(".")
    return ".".join(parts[-2:]) if len(parts) > 1 else q


def axis_arg(call: ast.Call, q: str) -> Optional[ast.AST]:
    """The axis-name argument expression of a collective/axis-user call,
    or None when the call has no axis argument (host collectives,
    facade barrier/broadcast without an explicit kwarg)."""
    pos = LAX_COLLECTIVES.get(q, LAX_AXIS_USERS.get(
        q, FACADE_COLLECTIVES.get(q)))
    if pos is not None and len(call.args) > pos:
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg in AXIS_KWARGS:
            return kw.value
    return None


def literal_axes(node: Optional[ast.AST]) -> Optional[FrozenSet[str]]:
    """The set of axis names a literal expression denotes: a string, or a
    tuple/list/set of strings. None for non-literal expressions (a
    variable axis is the caller's contract, not this call site's)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        names = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                names.append(e.value)
            else:
                return None
        return frozenset(names)
    return None


AxisContext = Union[FrozenSet[str], str]     # frozenset of names | UNKNOWN
