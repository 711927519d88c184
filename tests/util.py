"""Shared test fixtures: tiny models + random data.

Mirrors the reference's tests/unit/simple_model.py model zoo.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn


class SimpleModel(nn.Module):
    """Classification MLP whose loss is directly returned (DeepSpeed contract)."""
    hidden: int = 32
    nclass: int = 8
    nlayers: int = 2

    @nn.compact
    def __call__(self, batch, train=False):
        x, y = batch["x"], batch["y"]
        h = x
        for _ in range(self.nlayers):
            h = nn.relu(nn.Dense(self.hidden)(h))
        logits = nn.Dense(self.nclass)(h)
        logp = nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(y, self.nclass) * logp, axis=-1))


def random_batch(batch_size: int, dim: int = 16, nclass: int = 8, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch_size, dim).astype(np.float32)
    y = (x[:, :nclass].argmax(-1)).astype(np.int32)  # learnable labels
    return {"x": x, "y": y}


def batch_stream(batch_size: int, dim: int = 16, nclass: int = 8, seed: int = 0):
    i = seed
    while True:
        yield random_batch(batch_size, dim, nclass, seed=i)
        i += 1


def tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    ok = jax.tree.map(
        lambda x, y: np.allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)
    return all(jax.tree.leaves(ok))


def require_devices(n: int):
    """Skip when the active platform exposes fewer than n devices (the
    reference's requires_cuda_env pattern, tests/unit/common.py:78 — here
    the axis is device count: DSTPU_TEST_PLATFORM=tpu on a single chip
    cannot host the virtual multi-chip meshes the CPU suite uses)."""
    import jax
    import pytest
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices; platform has {len(jax.devices())}")


@contextlib.contextmanager
def global_mesh(mm):
    """``mm`` as the session's global mesh for the block: the model's own
    sharding constraints resolve against whatever the last test of the
    worker left there, not against the mesh an engine was handed."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(mm)
    try:
        yield mm
    finally:
        mesh_mod.set_global_mesh(before)


@contextlib.contextmanager
def zero3_engine_on_four(model, cfg, batch, *, micro: int, gas: int,
                         tp: int = 1, **zero):
    """The four-chip training cell's kind of engine (``initialize``: ZeRO-3,
    bf16 without master weights, bf16 accumulation, a ``fused_loss`` model)
    on four virtual CPU devices, dp ``4 / tp`` x tp ``tp`` (the model's own
    tensor-parallel rules); that mesh is the global one for the block.
    ``zero``: further keys of ``zero_optimization``."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import fused_loss_passthrough
    from deepspeed_tpu.parallel.mesh import MeshManager
    config = {"train_batch_size": micro * gas * (4 // tp),
              "train_micro_batch_size_per_gpu": micro,
              "gradient_accumulation_steps": gas,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
              "zero_optimization": {"stage": 3, **zero},
              "bf16": {"enabled": True, "master_weights": False},
              "data_types": {"grad_accum_dtype": "bf16"}}
    kw = {}
    if tp > 1:
        config["tensor_parallel"] = {"tp_size": tp}
        kw["sharding_rules"] = cfg.tp_rules()
    with global_mesh(MeshManager(devices=jax.devices()[:4],
                                 tp_size=tp)) as mm:
        yield ds.initialize(
            model=model, config=config, loss_fn=fused_loss_passthrough,
            example_batch=batch, mesh_manager=mm, **kw)[0]


def collectives(hlo_text: str):
    """[(opcode, result type, op_name, replica groups)] of every collective
    of an optimized HLO module's text (the type as written, layouts and all:
    a combined collective's is a tuple); a fusion that calls one (the TPU
    compiler's ``all-reduce-scatter``) counts as that, its groups ``None``
    (they are written in the called computation)."""
    import re
    kinds = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
             "collective-permute", "all-reduce-scatter")
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        kind = m.group(2)
        if kind == "fusion":
            called = re.search(r"calls=%?([a-z\-]+)", line)
            kind = called.group(1).rstrip("-") if called else kind
        kind = kind.removesuffix("-start")
        if kind in kinds:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((kind, m.group(1), name.group(1) if name else "",
                        _replica_groups(line)))
    return out


def _replica_groups(line: str):
    """An instruction's ``replica_groups`` as tuples of partition numbers,
    from either way XLA writes them (``{{0,2},{1,3}}`` or the iota form
    ``[2,2]<=[2,2]T(1,0)``); ``None`` where the line has none."""
    import re
    import numpy as np
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    if m:
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(d) for d in m.group(4).split(",")])
        return [tuple(g) for g in
                ids.reshape(int(m.group(1)), int(m.group(2))).tolist()]
    m = re.search(r"replica_groups=\{((?:\{[\d,]*\},?)+)\}", line)
    if m:
        return [tuple(int(d) for d in g.split(","))
                for g in re.findall(r"\{([\d,]+)\}", m.group(1))]
    return None


def crosses(groups, mesh, axes) -> bool:
    """Does a collective with these replica groups (partition numbers in
    the order of ``mesh.devices.flat``) move data along any of the mesh
    axes ``axes``? Unknown groups count as crossing."""
    import numpy as np
    if groups is None:
        return True
    dims = [mesh.axis_names.index(a) for a in axes]
    where = lambda i: tuple(np.unravel_index(i, mesh.devices.shape)[d]
                            for d in dims)
    return any(len({where(i) for i in g}) > 1 for g in groups)


def in_loss_loop(op_name: str) -> bool:
    """Does the op lie in the body of the fused loss's chunk loop (forward
    or backward)?"""
    return "/loss/" in op_name and "while/body" in op_name.split("/loss/")[-1]
