"""The chip's compiler, asked without the chip: every kernel of the main path
compiles for a DESCRIBED TPU v5e at the widths ``chip_smoke.py`` runs, and
the serving loop's two whole programs compile at the benchmark cell's widths
with the KV pool updated in place (no whole-pool copy in the optimized HLO).

Interpret mode cannot see what Mosaic refuses (block shapes the tiling
rules reject, too much fast memory, a kernel the partitioner cannot split):
before PR 21 the int8 paged-attention tier and ``quant_matmul`` had passed
every interpret-mode test and never compiled. Nothing runs here — these
tests say nothing about results or times.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file), in the test's own process, with the persistent
compilation cache off. The dispatch wrappers ask ``jax.default_backend()``
and see the CPU, so the tests call the kernel entries or steer the wrapper
with monkeypatch — the program has no option for it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# gpt2-1.3b serving widths (chip_smoke.FULL): 24 layers, 16 heads x 128,
# block 32, pool 1024 blocks, 8 lanes, 32 blocks per sequence
L, NH, HD, BS, NB, B, NBK = 24, 16, 128, 32, 1024, 8, 32


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory on one described chip; the persistent cache
    is off for the module (an entry written for a described chip cannot be
    read back without one, and the retry warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_scopes(text):
    import re
    return [re.search(r'op_name="([^"]+)"', line).group(1)
            for line in text.splitlines()
            if "tpu_custom_call" in line and "custom-call(" in line]


def _kernels(fn, *args):
    """Compile for the described chip; the kernel scopes in the program."""
    return _kernel_scopes(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("heads,head_dim", [(16, 128), (32, 64)])
def test_flash_fwd_bwd_compiles(chip, heads, head_dim):
    from deepspeed_tpu.ops.pallas.flash_attention import _flash
    x = chip((2, heads, 1024, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        # the kernel entry at the blocks flash_attention() picks for S=1024
        out = _flash(q, k, v, (None, None, None), heads, True,
                     head_dim ** -0.5, 1024, 1024, 1024, 1024, 0, 0.0,
                     False, False)
        return out.astype(jnp.float32).sum()

    names = _kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    for scope in ("flash_attention_fwd", "flash_attention_bwd_dq",
                  "flash_attention_bwd_dkv"):
        assert any(scope in n for n in names), (scope, names)


def test_flash_masked_wrapper_compiles(chip, monkeypatch):
    """bert-large's padded-batch leg (S 2048, D 64, key mask) through the
    public wrapper, steered onto the kernel path from the test."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = chip((4, 16, 2048, 64), jnp.bfloat16)
    m = chip((4, 1, 1, 2048), jnp.bool_)

    def loss(q, k, v, m):
        return flash_attention(q, k, v, causal=False,
                               mask=m).astype(jnp.float32).sum()

    names = _kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, m)
    assert len(names) == 3, names


# layers, query heads, stored (KV) heads, head_dim, block, pool blocks, lanes,
# table: the serving widths of chip_smoke.py, of the benchmark's four
# serving cells with K/V pools (three of them grouped-query models: four and
# eight query heads to a stored head; the last one's chunk under a learned
# indexer's selection), and of a preset whose heads are narrower than a lane
# tile
_PAGED_SHAPES = {
    "gpt2-1.3b": (L, NH, NH, HD, BS, NB, B, NBK),
    "serve-olmoe-1b-7b-l8-gen": (8, 16, 16, 128, 32, 2048, 64, 128),
    "serve-mistral-7b-l16-chat": (16, 32, 8, 128, 32, 384, 32, 40),
    "serve-k-exaone-236b-ep8-l5-mixed": (5, 64, 8, 128, 32, 1024, 32, 128),
    "serve-keye-vl2-30b-ep8-l8-longdoc": (8, 32, 4, 128, 32, 8192, 16, 800),
    "llama-1.1b": (22, 32, 4, 64, 32, 256, 8, 32)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", list(_PAGED_SHAPES))
def test_paged_attention_stacked_pool_compiles(chip, cell, quant):
    """The serving decode kernel on the stacked [L, kv heads, blocks, bs, hd]
    pool (a grouped-query model's query heads are rows of their stored
    head's tile) with a traced layer index and window, its K/V pages copied by the
    kernel itself out of the pool where it lies; int8 adds the per-slot
    scale rows (whole 128-lane tiles: a (1, block_size) row the compiler
    refused before PR 21 as a block and refuses still as a manual copy).
    The kernel's grid is lanes x head groups: no axis as long as the table
    (PR 28: four grid steps in five were dead), and the program round it
    has no loop and makes no array of a pool's size. Heads narrower than
    128 lanes keep the table's axis: the compiler refuses a kernel's own
    copy of part of the 128-lane row it pads such a pool to."""
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention,
                                                          scale_rows)
    L, NH, KVH, HD, BS, NB, B, NBK = _PAGED_SHAPES[cell]
    q = chip((B, NH, 1, HD), jnp.bfloat16)
    pool = chip((L, KVH, NB, BS, HD), jnp.int8 if quant else jnp.bfloat16)
    bt, lens, li = (chip((B, NBK), jnp.int32), chip((B,), jnp.int32),
                    chip((), jnp.int32))
    if quant:
        # the scales as serving.model_runner carries them through its loop
        sc = chip(jax.eval_shape(
            lambda: scale_rows(jnp.zeros((L, KVH, NB * BS, 1)),
                               pool.shape)).shape, jnp.float32)
        fn, args = (lambda q, k, v, bt, lens, li, ks, vs: paged_attention(
            q, k, v, bt, lens, layer_idx=li, window=li, k_scale=ks,
            v_scale=vs)), (q, pool, pool, bt, lens, li, sc, sc)
    else:
        fn, args = (lambda q, k, v, bt, lens, li: paged_attention(
            q, k, v, bt, lens, layer_idx=li, window=li)), (
            q, pool, pool, bt, lens, li)
    grids = [e.params["grid_mapping"].grid
             for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(grids) == 1 and grids[0][0] == B, grids
    assert grids[0][2:] == (() if HD % 128 == 0 else (NBK,)), grids
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = _kernel_scopes(text)
    assert len(names) == 1 and "paged_attention" in names[0], names
    results = _results(text)
    assert not [r for r in results if r[1] == "while"], results
    # nothing as large as one layer of a pool is made (int8: nor its scales).
    # Narrow heads are not held to it: as a bare call's argument such a pool
    # is copied whole to the lane-padded layout the kernel takes
    least = KVH * NB * (128 if quant else BS * HD)
    big = [r for r in results if r[3] >= least and r[1] not in (
        "parameter", "get-tuple-element", "tuple", "bitcast")]
    assert not big or HD % 128, big


@pytest.mark.parametrize("cell,T", [
    (cell, T) for cell in ("serve-olmoe-1b-7b-l8-gen",
                           "serve-mistral-7b-l16-chat")
    for T in range(32, 257, 32)] + [
    # every chunk shape pads to the one tile: the smallest and the largest
    ("serve-k-exaone-236b-ep8-l5-mixed", 32),
    ("serve-k-exaone-236b-ep8-l5-mixed", 256),
    ("serve-keye-vl2-30b-ep8-l8-longdoc", 32),
    ("serve-keye-vl2-30b-ep8-l8-longdoc", 256)])
def test_paged_attention_prefill_chunk_compiles(chip, cell, T):
    """The same kernel under a prefill chunk's T query rows a lane, at the
    four serving cells' widths and tables and every chunk shape their loops
    send (the
    multiples of the block up to ``prefill_chunk_tokens``, each padded to the
    one tile of 256 rows the kernel is traced at; a grouped-query model's
    tile is its stored head's whole group, mistral's 4 x 256 rows, or half
    of K-EXAONE's and Keye's 8 x 256; Keye's under its indexer's selection,
    the scores of a copy group's keys copied beside its pages): heads a
    program shrink with the rows and a turn takes 512 keys (PR 51) so that
    the accumulator, the running rows and one head's scores fit the chip's
    scoped VMEM beside the page buffers, which only this compiler can say.
    One kernel, one lane, no loop round it and nothing as large as a layer
    of the pool."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _pages_per_group, _program_heads, _query_rows, paged_attention)
    from deepspeed_tpu.ops.pallas.sparse_select import Selection
    L, NH, KVH, HD, BS, NB, _, NBK = _PAGED_SHAPES[cell]
    q = chip((1, NH, T, HD), jnp.bfloat16)
    pool = chip((L, KVH, NB, BS, HD), jnp.bfloat16)
    bt, lens, li = (chip((1, NBK), jnp.int32), chip((1,), jnp.int32),
                    chip((), jnp.int32))
    fn = lambda q, k, v, bt, lens, q0, li, *sel: paged_attention(
        q, k, v, bt, lens, layer_idx=li, window=li, q_start=q0,
        select=Selection(*sel) if sel else None)
    args = (q, pool, pool, bt, lens, lens, li)
    if "keye" in cell:
        args += (chip((1, T, NBK * BS), jnp.float32),
                 chip((1, T), jnp.float32), chip((1, T), jnp.int32))
    group = NH // KVH
    hg, gq = _program_heads(NH, KVH, BS, HD, 2, T)
    rows = gq * _query_rows(T)              # of one stored head's tile
    assert rows == gq * 256 and hg * rows <= 1024 and KVH % hg == 0
    assert (hg, gq) == {1: (4, 1), 4: (1, 4), 8: (1, 4)}[group]
    assert _pages_per_group(hg * gq, BS, HD, 2, NBK, False, T) * BS == 512
    # the call is a jitted one, shared by the chunk shapes: one level down
    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "jit"]
    grids = [e.params["grid_mapping"].grid
             for c in calls for e in c.params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert grids == [(1, KVH // hg * (group // gq))], grids
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = _kernel_scopes(text)
    assert len(names) == 1 and "paged_attention" in names[0], names
    results = _results(text)
    assert not [r for r in results if r[1] == "while"], results
    big = [r for r in results if r[3] >= KVH * NB * BS * HD and r[1] not in (
        "parameter", "get-tuple-element", "tuple", "bitcast")]
    assert not big, big


@pytest.mark.parametrize("K,N", [(2048, 6144), (2048, 8192), (8192, 2048)],
                         ids=["qkv", "mlp_fc", "mlp_proj"])
def test_quant_matmul_compiles(chip, monkeypatch, K, N):
    """The three 1.3B projections of the int8 weight tier (the (1, 128)
    scale tile of a 2-D [K/256, N] array was refused before PR 21)."""
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul
    from deepspeed_tpu.quant_format import QUANT_BLOCK
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    names = _kernels(quant_matmul, chip((B, 1, K), jnp.bfloat16),
                     chip((K, N), jnp.int8),
                     chip((K // QUANT_BLOCK, N), jnp.float32))
    assert len(names) == 1 and "quant_matmul" in names[0], names


def test_sliding_window_kernel_compiles(chip):
    """The block-skip layout kernel a pure causal window routes to."""
    from deepspeed_tpu.ops.attention import sliding_window_attention
    x = chip((2, 16, 2048, 128), jnp.bfloat16)
    names = _kernels(lambda q, k, v: sliding_window_attention(q, k, v, 512),
                     x, x, x)
    assert len(names) == 1 and "block_sparse_attention" in names[0], names


@pytest.mark.parametrize("layout", ["dp4", "dp2_tp2", "dp2_manual_tp2"])
def test_flash_runs_per_shard_on_a_four_chip_mesh(topo, chip, monkeypatch,
                                                  layout):
    """A Mosaic kernel cannot be partitioned by the compiler; on a mesh of
    several chips the dispatch layer (ops/attention.py) runs it per shard
    under shard_map (PR 21: the ZeRO-3 dp=4 step was refused here, not on
    the chip). dp4 is the smoke's --multichip layout; dp2_tp2 composes
    tensor parallelism (heads over "model"); dp2_manual_tp2 is the
    comm-plan step's shape — batch axes manual already, "model" still auto."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.attention import attention
    from deepspeed_tpu.parallel.mesh import BATCH_AXES, MESH_AXES, TP_AXIS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dp, tp = (4, 1) if layout == "dp4" else (2, 2)
    mesh = Mesh(np.array(topo.devices).reshape(1, dp, 1, 1, tp), MESH_AXES)
    spec = P(BATCH_AXES, TP_AXIS if tp > 1 else None)
    x = jax.ShapeDtypeStruct((8, 16, 1024, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))

    def loss(q, k, v):
        return attention(q, k, v, impl="flash").astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2))
    if layout == "dp2_manual_tp2":
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(BATCH_AXES),) * 3,
                           out_specs=(P(BATCH_AXES),) * 3,
                           axis_names=frozenset(BATCH_AXES), check_vma=False)
    names = _kernels(fn, x, x, x)
    assert len(names) == 3 and all("shard_map" in n for n in names), names


def _results(text):
    """(computation, opcode, dtype, elements) of every instruction of an
    optimized HLO module's text, fused computations included."""
    import math
    import re
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = "ENTRY" if line.startswith("ENTRY") else head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            out.append((comp, m.group(3), m.group(1), math.prod(dims)))
    return out


def _prefill_rides_the_kernel(cfg, pools, bs, chunk=256):
    """Gauge ``paged.prefill_path`` as the engine works it out
    (``serving/engine.py::_note_prefill_path``): every prefill program of
    the loop, a chunk shape each, goes the kernel's way."""
    from deepspeed_tpu.ops.attention import paged_attention_path
    pool = pools["k"]
    for T in range(bs, chunk + 1, bs):
        assert paged_attention_path(
            (1, cfg.num_heads, T, cfg.head_dim),
            pool.shape[:2] + (pool.shape[2] // bs, bs, cfg.head_dim),
            stacked=True, quant="k_scale" in pools) == ("kernel", None)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_serving_step_updates_the_pool_in_place(chip, monkeypatch, program,
                                                quant):
    """The serving loop's two programs (``serving.engine.step_programs``, as
    the engine jits them: pools donated) at the benchmark cell's widths,
    mistral-7b-l16 with 32 lanes over a 384 x 32 pool stored at the model's
    8 KV heads (``[16, 8, slots, 128]``, a quarter of what 32 query heads
    took until PR 44): no instruction makes
    a whole K/V pool, or a whole layer of one, by ``copy``, ``transpose``,
    ``scatter`` or ``dynamic-slice`` (a prefill chunk sliced both pools' layer
    out for the gather reference until PR 37: 5.7 ms of a chunk step), and
    the program's temporaries are a fraction of one pool. Before PR 25 the K/V scatter left the pool in a
    layout the paged kernel does not read: six whole-pool copies a decode
    step, 2 x 16 of them inside the layer loop, ``temp_size`` of two pools
    (PERF.md, PR 25)."""
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, NH, KVH, HD, BS, NB, B, NBK = 16, 32, 8, 128, 32, 384, 32, 40
    model, cfg = build_model(TransformerConfig(
        vocab_size=32000, max_seq_len=32768, hidden_size=NH * HD,
        num_layers=L, num_heads=NH, num_kv_heads=KVH, mlp_dim_override=14336,
        layer_norm_eps=1e-5, norm="rmsnorm", gated_mlp=True,
        activation="silu", pos_embed="rotary", rotary_interleaved=False,
        use_bias=False, tie_embeddings=False, layer_windows=(4096,) * L,
        dtype=jnp.bfloat16))
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: ensure_scan_layout(jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]), L)))
    pools = on_chip(jax.eval_shape(lambda: init_pool(
        cfg, NB, BS, jnp.int8 if quant else jnp.bfloat16)))
    assert pools["k"].shape == (L, KVH, NB * BS, HD)
    assert not quant or pools["k_scale"].shape == (L, KVH, NB * BS, 1)
    _prefill_rides_the_kernel(cfg, pools, BS)
    lanes = B if program == "decode" else 1
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        # the previous decode call's and the last prefill call's outputs,
        # where the program finds the tokens the host never held (PR 40)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(256), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    # the pools go in and come out where they lie (donated, aliased)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())

    layer = KVH * NB * BS * HD
    moved = [r for r in _results(text)
             if r[1] in ("copy", "transpose", "scatter") and r[3] >= layer]
    assert not moved, moved
    # nor is a layer of a pool sliced out of it: since PR 37 a chunk's
    # attention reads the pool through the block table, as a decode step's
    sliced = [r for r in _results(text)
              if r[1] == "dynamic-slice" and r[3] == layer]
    assert not sliced, sliced
    # the int8 tier's scales reach the kernel a block a row of whole
    # 128-lane tiles, which is not the layout they have at the jit boundary:
    # they change layout there (four small conversions a step), never in the
    # loop, neither as they come nor as the loop carries them
    scales = (L * KVH * NB * BS, L * KVH * NB * 128)
    inside = [r for r in _results(text)
              if r[0] != "ENTRY" and r[2] == "f32" and r[3] in scales
              and r[1] in ("copy", "transpose", "scatter", "reshape", "pad")]
    assert not inside, inside
    pool_bytes = L * layer * (1 if quant else 2)
    # int8: the two scale pools live lane-padded in the loop (2 x 100 MB,
    # a quarter of an int8 pool), outside the donated buffers
    share = 0.5 if quant else 0.1 if program == "decode" else 0.25
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < share * pool_bytes, (temp, pool_bytes)
    names = _kernel_scopes(text)
    assert len(names) == 1 and "paged_attention" in names[0], names


@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_olmoe_serving_step_is_dropless_and_in_place(chip, monkeypatch,
                                                     program):
    """The serving loop's two programs at the OLMoE cell's widths
    (olmoe-1b-7b-l8: 64 experts of 1024, top-8; 64 lanes over a 2048 x 32
    pool): the expert matmuls are the megablox kernel (``gmm`` custom calls
    under ``block.mlp/experts``), three a layer body, at the tile sizes
    ``moe/dropless.py`` picks; no instruction makes an
    array of ``rows x experts x width`` (a capacity-padded or all-experts
    dispatch would: 512 decode rows x 64 x 1024); the counts leave with the
    tokens in one int32 vector; and the pool is still updated in place."""
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, NH, HD, BS, NB, B, NBK, E, K, M = 8, 16, 128, 32, 2048, 64, 128, 64, \
        8, 1024
    model, cfg = build_model(TransformerConfig(
        vocab_size=50304, max_seq_len=4096, hidden_size=NH * HD,
        num_layers=L, num_heads=NH, num_kv_heads=NH, mlp_dim_override=M,
        layer_norm_eps=1e-5, norm="rmsnorm", gated_mlp=True,
        activation="silu", pos_embed="rotary", rotary_interleaved=False,
        use_bias=False, tie_embeddings=False, qk_norm="projection",
        moe_experts=E, moe_k=K, moe_dropless=True, moe_norm_topk=False,
        dtype=jnp.bfloat16))
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: ensure_scan_layout(jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]), L)))
    pools = on_chip(jax.eval_shape(lambda: init_pool(cfg, NB, BS,
                                                     jnp.bfloat16)))
    lanes = B if program == "decode" else 1
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        # the previous decode call's and the last prefill call's outputs,
        # where the program finds the tokens the host never held (PR 40)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(256), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    (out, picks), _ = compiled.out_info      # the tokens, then the counts
    assert out.shape == (lanes + L * E,) and out.dtype == jnp.int32
    # ... and beside them the call's picks, left on the device
    assert picks.shape == (L, B if program == "decode" else 256, K) \
        and picks.dtype == jnp.int32
    kernels = _kernel_scopes(text)
    grouped = [k for k in kernels if "jit(gmm)" in k]
    assert len(grouped) == 3, kernels
    assert all("block.mlp/experts" in k for k in grouped), grouped
    assert len(kernels) == 4, kernels                            # + paged
    rows = (B if program == "decode" else 256) * K
    made = [r for r in _results(text) if r[1] not in (
        "parameter", "get-tuple-element", "while", "tuple", "bitcast")]
    # the expert stack goes to the kernel whole: nothing makes a layer's
    # experts (a slice of the stack is a copy of 0.8 GB a layer a step:
    # three `dynamic-slice_bitcast_fusion bf16[64,2048,1024]`, 39% of the
    # first chip trace, PERF.md section 6, PR 26) or the stack itself
    import re
    sliced = [line.strip()[:160] for line in text.splitlines() if re.search(
        r"= bf16\[(?:%d,)?%d,(?:%d,%d|%d,%d)\]\S* (?!parameter|bitcast|"
        r"get-tuple-element)" % (L, E, NH * HD, M, M, NH * HD), line)]
    assert not sliced, sliced
    big = [r for r in made
           if r[3] >= rows * E * M and r[3] != 50304 * NH * HD]   # the head
    layer = NH * NB * BS * HD
    assert not [r for r in big if r[3] < layer], big
    # the pool keeps the one layout it has at the jit boundary in BOTH
    # programs. Until PR 37 the prefill's gather reference had the chip's
    # compiler carry it through the layer loop blocks-major, {4,3,1,2,0},
    # and copy both pools whole on the way in and out (found here by PR 26
    # and kept as an xfail: four copies of 2.1 GB and sixteen layer slices,
    # 39 ms of a 61 ms chunk step, PERF.md section 6, PR 37)
    moved = [r for r in big if r[3] >= layer and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] == layer)]
    assert not moved, moved


@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_k_exaone_serving_step_holds_a_share_in_place(chip, monkeypatch,
                                                      program):
    """The serving loop's two programs at the K-EXAONE cell's widths, read
    from the benchmark's own files (k-exaone-236b-ep8-l5: hidden 6144, 64
    query heads over 8 stored, 16 of 128 experts of 2048 held, top-8, a shared expert, a
    leading dense layer of 18432; 32 lanes over a 1024 x 32 pool): the held
    experts' matmuls are the megablox kernel, three in the sparse stack's
    layer body; each of the two stacks' bodies has its paged-attention call;
    the counts and picks are over the 4 SPARSE layers and the router's 128
    outputs; nothing copies the expert stack or slices a layer's experts
    out of it (the leading stack's scan must not either); the pool is
    updated in place; and weights, pool and temporaries fit the chip."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell("serve-k-exaone-236b-ep8-l5-mixed")
    serving = cell.system["serving"]
    BS, NB, B, NBK = (serving[k] for k in (
        "block_size", "pool_blocks", "max_batch", "max_blocks_per_seq"))
    model, cfg = build_model(TransformerConfig(
        **harness.load_family("exaone_moe").model_kwargs(cell.config),
        dtype=jnp.bfloat16))
    H, NH, HD, M, K = cfg.hidden_size, cfg.num_heads, cfg.head_dim, \
        cfg.mlp_dim, cfg.moe_k
    LS, E, HELD = cfg.sparse_layers, cfg.moe_experts, cfg.moe_held_count
    assert (cfg.num_layers, LS, E, HELD, H, M) == (5, 4, 128, 16, 6144, 2048)
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])))
    assert params["blocks"]["moe"]["experts"]["fc"]["kernel"].shape == \
        (LS, HELD, H, M)
    pools = on_chip(jax.eval_shape(lambda: init_pool(cfg, NB, BS,
                                                     jnp.bfloat16)))
    KVH = cfg.kv_heads
    assert (NH, KVH) == (64, 8)
    assert pools["k"].shape == (cfg.num_layers, KVH, NB * BS, HD)
    _prefill_rides_the_kernel(cfg, pools, BS)
    lanes = B if program == "decode" else 1
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(256), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    (out, picks), _ = compiled.out_info
    assert out.shape == (lanes + LS * E,) and out.dtype == jnp.int32
    assert picks.shape == (LS, B if program == "decode" else 256, K)
    kernels = _kernel_scopes(text)
    grouped = [k for k in kernels if "jit(gmm)" in k]
    assert len(grouped) == 3, kernels
    assert all("block.mlp/experts" in k for k in grouped), grouped
    paged = [k for k in kernels if "paged_attention" in k]
    assert len(paged) == 2 and len(kernels) == 5, kernels
    # the expert stack goes to the kernel whole, from both loops: nothing
    # makes the stack [4, 16, 6144, 2048] or a layer's experts of it
    import re
    sliced = [line.strip()[:160] for line in text.splitlines() if re.search(
        r"= bf16\[(?:%d,)?%d,(?:%d,%d|%d,%d)\]\S* (?!parameter|bitcast|"
        r"get-tuple-element)" % (LS, HELD, H, M, M, H), line)]
    assert not sliced, sliced
    made = [r for r in _results(text) if r[1] not in (
        "parameter", "get-tuple-element", "while", "tuple", "bitcast")]
    assert not [r for r in made if r[3] >= HELD * H * M
                and r[2] == "bf16" and r[3] % (HELD * H * M) == 0
                and r[3] <= LS * HELD * H * M], made
    layer = KVH * NB * BS * HD
    moved = [r for r in made if r[3] >= layer and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] == layer)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())
    held_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held_bytes < 15.0e9, (mem.argument_size_in_bytes,
                                 mem.temp_size_in_bytes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell_name", ["serve-mistral-7b-l16-chat",
                                       "serve-k-exaone-236b-ep8-l5-mixed"])
def test_mixed_step_is_one_program_in_place(chip, monkeypatch, cell_name,
                                            quant):
    """The program of a step that advances a prefill chunk where the cache
    is not latent (``step_programs(..., mixed=True)``, PR 57), at the two
    claimed cells' shapes read from the benchmark's own files: a chunk's 256
    rows beside 32 lanes, over a bf16 pool and an int8 one. The rows of both
    kinds go through the matmuls together (288 rows, no matmul of 256 or of
    32); each layer body has the paged kernel twice, the chunk form and the
    decode form; the outputs are the two programs' (the lanes' vector, the
    chunk's, a mixture's counts behind each and its picks beside); and with
    both kinds' writes in one body the pool still keeps the one layout it
    has at the jit boundary: no whole pool, and no layer of one, is copied,
    transposed, scattered or sliced out (PR 53 saw the chip's compiler name
    an int8 pool blocks-major here and copy K whole a layer)."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell(cell_name)
    serving = cell.system["serving"]
    BS, NB, B, NBK, T = (serving[k] for k in (
        "block_size", "pool_blocks", "max_batch", "max_blocks_per_seq",
        "prefill_chunk_tokens"))
    assert (B, T) == (32, 256)
    model, cfg = build_model(TransformerConfig(
        **harness.load_family(cell.config["family"]).model_kwargs(
            cell.config), dtype=jnp.bfloat16))
    assert not cfg.kv_lora_rank
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: ensure_scan_layout(jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]),
        cfg.num_layers)))
    pools = on_chip(jax.eval_shape(lambda: init_pool(
        cfg, NB, BS, jnp.int8 if quant else jnp.bfloat16)))
    _prefill_rides_the_kernel(cfg, pools, BS)
    layout = StepLayout(NBK)
    mixed = step_programs(cfg, BS, NBK, mixed=True)[2]
    fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    compiled = jax.jit(mixed, donate_argnums=(1,)).lower(
        params, pools, chip((layout.prefill_words(T)
                             + layout.decode_words(B),), jnp.int32),
        *fed).compile()
    text = compiled.as_text()
    (lanes, first), _ = compiled.out_info
    counts = cfg.sparse_layers * cfg.moe_experts if cfg.moe_is_dropless else 0
    if counts:
        (lanes, lane_picks), (first, chunk_picks) = lanes, first
        assert lane_picks.shape == (cfg.sparse_layers, B, cfg.moe_k)
        assert chunk_picks.shape == (cfg.sparse_layers, T, cfg.moe_k)
    assert lanes.shape == (B + counts,) and first.shape == (1 + counts,)
    # the decode program's own operands: its outputs feed the next call
    assert [lanes.shape, first.shape] == [f.shape for f in fed]
    # one batch of rows through the matmuls; the head on the 33 it reads
    H = cfg.hidden_size
    widths = {(m.group(1), m.group(2)) for m in re.finditer(
        r"= bf16\[(?:1,)?(\d+),(\d+)\]\S* (?:fusion|convolution|dot)\(",
        text)}
    rows = {int(r) for r, w in widths if int(w) >= H}
    assert T + B in rows and not rows & {T, B}, sorted(widths)
    assert re.search(r"\[(?:1,)?%d,%d\]" % (B + 1, cfg.vocab_size), text)
    # each stack's layer body: the kernel's chunk form and its decode form
    kernels = _kernel_scopes(text)
    paged = [k for k in kernels if "paged_attention" in k]
    stacks = 2 if cfg.dense_layers else 1
    assert len(paged) == 2 * stacks, kernels
    assert sum("attend/chunk" in k for k in paged) == stacks \
        and sum("attend/lanes" in k for k in paged) == stacks, paged
    assert len(kernels) == len(paged) + (3 if counts else 0), kernels
    layer = cfg.kv_heads * NB * BS * cfg.head_dim
    moved = [r for r in _results(text) if r[3] >= layer and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] == layer)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9, (
        mem.argument_size_in_bytes, mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_keye_serving_step_selects_keys_in_place(chip, monkeypatch, program):
    """The serving loop's two programs at the Keye-VL-2.0 cell's widths,
    read from the benchmark's own files (keye-vl2-30b-ep8-l8: hidden 2048,
    32 query heads over 4 stored, a 16 x 64 indexer with top-k 2048, 16 of
    128 experts of 768 held; 16 lanes over an 8192 x 32 pool, tables of 800
    blocks): the layer body holds the indexer's two kernels, the paged
    kernel and the held experts' three grouped matmuls; the pool's third
    leaf (the indexer's keys) is updated in place with K and V; the picks
    carry the selection as bits behind the experts; and weights, pool and
    temporaries fit the chip."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell("serve-keye-vl2-30b-ep8-l8-longdoc")
    serving = cell.system["serving"]
    BS, NB, B, NBK = (serving[k] for k in (
        "block_size", "pool_blocks", "max_batch", "max_blocks_per_seq"))
    model, cfg = build_model(TransformerConfig(
        **harness.load_family("keye_vl2").model_kwargs(cell.config),
        dtype=jnp.bfloat16))
    L, E, K = cfg.num_layers, cfg.moe_experts, cfg.moe_k
    assert (L, E, cfg.moe_held_count, cfg.hidden_size, cfg.mlp_dim,
            cfg.num_heads, cfg.kv_heads, cfg.index_heads, cfg.index_head_dim,
            cfg.index_topk) == (8, 128, 16, 2048, 768, 32, 4, 16, 64, 2048)
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])))
    assert params["blocks"]["index_q"]["kernel"].shape == (L, 2048, 16 * 64)
    pools = on_chip(jax.eval_shape(lambda: init_pool(cfg, NB, BS,
                                                     jnp.bfloat16)))
    assert pools["ki"].shape == (L, 1, NB * BS, 128)    # 64 on 128 lanes
    _prefill_rides_the_kernel(cfg, pools, BS)
    rows = B if program == "decode" else 256
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(256), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    (out, picks), _ = compiled.out_info
    assert out.shape == ((B if program == "decode" else 1) + L * E,)
    assert picks.shape == (L, rows, K + NBK * BS // 32)
    kernels = _kernel_scopes(text)
    for name, n in (("jit(gmm)", 3), ("paged_attention", 1),
                    ("sparse_index_scores", 1), ("sparse_topk", 1)):
        assert len([k for k in kernels if name in k]) == n, (name, kernels)
    assert len(kernels) == 6, kernels
    # the top-k reads the scores where the score kernel wrote them: a chunk's
    # [256, 25600] and a decode call's [16, 25600] (a lane a row: the
    # decode-shaped score program's result) are that buffer itself or a
    # bitcast of it, never a padded or sliced copy, and no 8-row tile a lane
    # exists in the decode program
    call = next(line for line in text.splitlines()
                if "custom-call(" in line and "/sparse_topk" in line)
    fed = re.search(r"custom-call\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)",
                    call).group(1)
    source = next(line for line in text.splitlines()
                  if re.match(r"\s*%%%s = " % re.escape(fed), line))
    assert f"f32[{rows},{NBK * BS}]" in source, source
    assert re.search(r" bitcast\(%sparse_index_scores[\w.\-]*\)", source) \
        or "/sparse_index_scores" in source and "custom-call(" in source, \
        source
    assert f"f32[{B},8,{NBK * BS}]" not in text
    made = [r for r in _results(text) if r[1] not in (
        "parameter", "get-tuple-element", "while", "tuple", "bitcast")]
    layer = cfg.kv_heads * NB * BS * cfg.head_dim
    moved = [r for r in made if r[3] >= layer and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] == layer)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())
    held_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 6.0e9 < held_bytes < 12.0e9, (mem.argument_size_in_bytes,
                                         mem.temp_size_in_bytes)


#: gpt2-1.3b's six serving programs (``scripts/serving_program_text.py``:
#: the decode, the 256-row prefill and, as PR 57 left it and the script
#: lowers it since PR 60, the mixed step, bf16 and the int8 tier) as every
#: tree since PR 44 has lowered them, sha256 of the text, first 16 digits. A
#: PR for another model leaves them as they are (PR 26 was refused for a
#: dense path it had touched); one that means to change the dense programs
#: changes these with its reason. PR 51 changed the paged kernel's CHUNK
#: form for every model (a turn of 512 keys, a head at a time): both prefill
#: programs; the decode programs are PR 44's still
_GPT2_PROGRAMS = {"gpt2-1.3b.decode": "da1edc35111a2336",
                  "gpt2-1.3b.prefill256": "36ca0b182b3cd0b3",
                  "gpt2-1.3b.mixed256": "3fb973366bb85346",
                  "gpt2-1.3b-int8.decode": "b2066bebbff05fcd",
                  "gpt2-1.3b-int8.prefill256": "5f8d1b0c90bf9aa6",
                  "gpt2-1.3b-int8.mixed256": "ed40ab4001efda60"}


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_gpt2_serving_programs_are_text_for_text_what_they_were(
        chip, monkeypatch, int8):
    """Latent attention, YaRN and the grouped router were added beside the
    dense path, not inside it, and PR 51's chunk form left the decode form
    alone: the lowered text of gpt2-1.3b's programs is what the table
    says."""
    import hashlib
    import importlib.util
    import os
    from benchmark import harness
    from jax._src import tpu_custom_call
    spec = importlib.util.spec_from_file_location(
        "serving_program_text", os.path.join(
            harness.ROOT, "scripts", "serving_program_text.py"))
    script = importlib.util.module_from_spec(spec)
    before = (tpu_custom_call._lower_mosaic_module_to_asm, list(os.sys.path))
    try:
        spec.loader.exec_module(script)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        script._strip_kernel_locations()
        gpt2 = harness.load_json(os.path.join(harness.HERE, "configs",
                                              "gpt2-1.3b.json"))
        got = {label: hashlib.sha256(text.encode()).hexdigest()[:16]
               for label, text in script.programs(
                   "gpt2-1.3b", gpt2, script.SMOKE_SERVING, int8=int8,
                   chip=chip)}
    finally:
        tpu_custom_call._lower_mosaic_module_to_asm, os.sys.path[:] = before
    assert got == {k: v for k, v in _GPT2_PROGRAMS.items()
                   if ("int8" in k) == int8}


@pytest.mark.parametrize("rows", [1536, 4000, 24576])
def test_latent_chunk_call_compiles_at_any_row_count(chip, rows):
    """A latent model's chunk call alone at DeepSeek-V2's widths (128 heads
    of 128 + 64, a latent of 512 on rows of 640 lanes, the cell's pool and
    table): the cell's own chunk, and a whole prompt handed to the kernel in
    ONE call (``serving.prefill_chunk_tokens`` 0, the default): a program
    holds a row tile of at most 1 536 rows, whatever the call's, so what it
    takes of VMEM is the cell's and Mosaic compiles it (before the row
    tiles went on the grid a program held the whole call: 12 KB a row, over
    the kernel's 48 MB limit from about 3 500 rows)."""
    from deepspeed_tpu.ops.pallas import latent_attention as la
    bf16, nh, bs, nb, nbk = jnp.bfloat16, 128, 32, 16384, 800
    n, per = la.chunk_tiles(rows)
    assert per <= 1536

    def call(qn, qp, wk, wv, pool, bt, lens, q0):
        return la.latent_chunk_attention(
            qn, qp, wk, wv, pool, bt, lens, sm_scale=0.1147, layer_idx=3,
            q_start=q0)

    text = jax.jit(call).lower(
        chip((1, nh, rows, 128), bf16), chip((1, nh, rows, 64), bf16),
        chip((nh, 512, 128), bf16), chip((nh, 512, 128), bf16),
        chip((5, 1, nb, bs, 640), bf16), chip((1, nbk), jnp.int32),
        chip((1,), jnp.int32), chip((1,), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "paged_attention_latent" in line]
    assert len(calls) == 1 and f"bf16[1,128,{n * per},128]" in calls[0], calls


@pytest.mark.parametrize("program",
                         ["decode", "prefill256", "prefill1536"])
def test_deepseek_serving_step_keeps_the_latent_pool_in_place(
        chip, monkeypatch, program):
    """The serving loop's two programs at the DeepSeek-V2 cell's widths,
    read from the benchmark's own files (deepseek-v2-ep8-l5: hidden 5120,
    128 heads over ONE stored row of 512 + 64 on 640 lanes, 20 of 160
    experts of 1536 held, a leading dense layer of 12288; 32 lanes over a
    16384 x 32 pool, tables of 800 blocks): each of the two stacks' bodies
    has its latent-attention call (a decode call's absorbed, a chunk's
    expanded inside the kernel), the sparse one the held experts' three
    grouped matmuls; the pool is ONE leaf, donated and updated in place, and
    no instruction copies it, a layer of it, or slices a layer out; the
    counts carry the kept groups behind the experts; and weights, pool and
    temporaries fit the chip. A prefill call brings whole 256-row tiles
    (``ServingEngine._prefill_rows``): the smallest program and the chunk's
    own."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell("serve-deepseek-v2-ep8-l5-longdoc")
    serving = cell.system["serving"]
    BS, NB, B, NBK, chunk = (serving[k] for k in (
        "block_size", "pool_blocks", "max_batch", "max_blocks_per_seq",
        "prefill_chunk_tokens"))
    assert program in ("decode", "prefill256", f"prefill{chunk}")
    model, cfg = build_model(TransformerConfig(
        **harness.load_family("deepseek_v2").model_kwargs(cell.config),
        dtype=jnp.bfloat16))
    L, LS, E, K, G = cfg.num_layers, cfg.sparse_layers, cfg.moe_experts, \
        cfg.moe_k, cfg.moe_groups
    assert (L, LS, E, cfg.moe_held, G, cfg.moe_topk_groups, cfg.hidden_size,
            cfg.mlp_dim, cfg.num_heads, cfg.head_dim, cfg.latent_width,
            cfg.latent_lanes) == (5, 4, 160, (0, 20), 8, 3, 5120, 1536, 128,
                                  192, 576, 640)
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])))
    assert params["blocks"]["attn_kv_b"]["kernel"].shape == (LS, 512, 32768)
    assert "attn_qkv" not in params["blocks"]
    pools = on_chip(jax.eval_shape(lambda: init_pool(cfg, NB, BS,
                                                     jnp.bfloat16)))
    assert set(pools) == {"ckv"} and \
        pools["ckv"].shape == (L, 1, NB * BS, 640)
    rows = B if program == "decode" else int(program[len("prefill"):])
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(rows), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    (out, picks), _ = compiled.out_info
    assert out.shape == ((B if program == "decode" else 1) + LS * (E + G),)
    assert picks.shape == (LS, rows, K)
    kernels = _kernel_scopes(text)
    assert len([k for k in kernels if "jit(gmm)" in k]) == 3, kernels
    latent = [k for k in kernels if "paged_attention" in k]
    assert len(latent) == 2 and len(kernels) == 5, kernels
    assert text.count("paged_attention_latent") >= 2
    # a decode call attends absorbed (the heads the rows of a lane's tile,
    # the latent's 512 lanes out, absorb matmuls beside it); a chunk
    # expanded inside the kernel: all of its rows a head, 128 lanes out
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "paged_attention" in line]
    out = "bf16[32,1,128,512]" if program == "decode" else \
        f"bf16[1,128,{rows},128]"
    assert len(calls) == 2 and all(f" = {out}" in c for c in calls), calls
    assert ("absorb" in text) == (program == "decode")
    made = [r for r in _results(text) if r[1] not in (
        "parameter", "get-tuple-element", "while", "tuple", "bitcast")]
    layer = NB * BS * 640
    moved = [r for r in made if r[3] >= layer and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] == layer)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())
    held_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 9.0e9 < held_bytes < 13.0e9, (mem.argument_size_in_bytes,
                                         mem.temp_size_in_bytes)


@pytest.mark.parametrize("program",
                         ["decode", "prefill256", "prefill1536"])
def test_deepseek_v32_serving_step_selects_latent_rows_in_place(
        chip, monkeypatch, program):
    """The serving loop's two programs at the DeepSeek-V3.2-Exp cell's
    widths, read from the benchmark's own files (deepseek-v32-exp-ep16-l5:
    hidden 7168, 128 heads over ONE stored row of 512 + 64 on 640 lanes, a
    64 x 128 indexer that picks 2048 of them, 16 of 256 experts of 2048 held
    (half a routing group), a leading dense layer of 18432; 32 lanes over a
    12288 x 32 pool, tables of 800 blocks): each of the two stacks' bodies
    has its index-score call, its top-k and its latent-attention call UNDER
    the selection (the scores and a row's threshold and tie position among
    the kernel's operands), the sparse one the held experts' three grouped
    matmuls; the pool is TWO leaves (``ckv`` + ``ki``), donated and updated
    in place, and no instruction copies either, a layer of one, or slices a
    layer out; no ``[rows, keys]`` mask is made beside the scores; every
    layer hands a row out (the dense layer's selection too); and weights,
    pool and temporaries fit the chip. A prefill call brings whole 256-row
    tiles (``ServingEngine._prefill_rows``): the smallest program and the
    chunk's own."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell("serve-deepseek-v32-exp-ep16-l5-longdoc")
    serving = cell.system["serving"]
    BS, NB, B, NBK, chunk = (serving[k] for k in (
        "block_size", "pool_blocks", "max_batch", "max_blocks_per_seq",
        "prefill_chunk_tokens"))
    from deepspeed_tpu.ops.pallas.latent_attention import chunk_tiles
    assert program in ("decode", f"prefill{chunk_tiles(1)[1]}",
                       f"prefill{chunk}")
    model, cfg = build_model(TransformerConfig(
        **harness.load_family("deepseek_v32").model_kwargs(cell.config),
        dtype=jnp.bfloat16))
    L, LS, E, K, G = cfg.num_layers, cfg.sparse_layers, cfg.moe_experts, \
        cfg.moe_k, cfg.moe_groups
    assert (L, LS, cfg.routed_layers, E, cfg.moe_held, G,
            cfg.moe_topk_groups, cfg.hidden_size, cfg.mlp_dim, cfg.num_heads,
            cfg.head_dim, cfg.latent_lanes, cfg.index_heads,
            cfg.index_head_dim, cfg.index_rope_dim, cfg.index_topk) == (
        5, 4, 5, 256, (0, 16), 8, 4, 7168, 2048, 128, 192, 640, 64, 128, 64,
        2048)
    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])))
    # the indexer's queries read the query latent, in both stacks
    assert params["blocks"]["index_q"]["kernel"].shape == (LS, 1536, 8192)
    assert params["dense_blocks"]["index_q"]["kernel"].shape == (
        1, 1536, 8192)
    pools = on_chip(jax.eval_shape(lambda: init_pool(cfg, NB, BS,
                                                     jnp.bfloat16)))
    assert {n: a.shape for n, a in pools.items()} == {
        "ckv": (L, 1, NB * BS, 640), "ki": (L, 1, NB * BS, 128)}
    rows = B if program == "decode" else int(program[len("prefill"):])
    decode, prefill = step_programs(cfg, BS, NBK)
    if program == "decode":
        fn, words = decode, StepLayout(NBK).decode_words(B)
        fed = [chip((token_words(cfg, n),), jnp.int32) for n in (B, 1)]
    else:
        fn, words, fed = prefill, StepLayout(NBK).prefill_words(rows), []
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, chip((words,), jnp.int32), *fed).compile()
    text = compiled.as_text()
    (out, picks), _ = compiled.out_info
    assert out.shape == ((B if program == "decode" else 1) + LS * (E + G),)
    assert picks.shape == (L, rows, K + NBK * BS // 32)
    kernels = _kernel_scopes(text)
    assert len([k for k in kernels if "jit(gmm)" in k]) == 3, kernels
    for scope in ("paged_attention", "sparse_index_scores", "sparse_topk"):
        assert len([k for k in kernels if scope in k]) == 2, (scope, kernels)
    assert len(kernels) == 9, kernels
    # the latent call takes the scores where the top-k left them, float32,
    # and the rows' threshold and tie position on 128 lanes
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "paged_attention_latent" in line]
    out = "bf16[32,1,128,512]" if program == "decode" else \
        f"bf16[1,128,{rows},128]"
    sel = (f"f32[{B},1,{NBK * BS}]", f"s32[{B},1,128]") \
        if program == "decode" else (f"f32[1,{rows},{NBK * BS}]",
                                     f"s32[1,{rows},128]")
    assert len(calls) == 2 and all(
        f" = {out}" in c and all(s in c for s in sel) for c in calls), calls
    made = [r for r in _results(text) if r[1] not in (
        "parameter", "get-tuple-element", "while", "tuple", "bitcast")]
    # (an activation of the chunk, [128, 1536, 448], lies between the two
    # leaves' layers in size: the smaller leaf is held by its exact sizes)
    ki, ckv = NB * BS * 128, NB * BS * 640
    moved = [r for r in made if (r[3] >= ckv or r[3] in (ki, L * ki)) and (
        r[1] in ("copy", "transpose", "scatter")
        or r[1] == "dynamic-slice" and r[3] in (ki, ckv))]
    assert not moved, moved
    # no [rows, keys] mask beside the scores
    masks = [r for r in made if r[2] == "pred" and "fused" not in r[0]
             and r[3] >= rows * NBK * BS // 8]
    assert not masks, masks
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools.values())
    held_bytes = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    least = 12.0e9 if rows in (B, chunk) else 11.5e9
    assert least < held_bytes < 14.0e9, (mem.argument_size_in_bytes,
                                         mem.temp_size_in_bytes)


@pytest.mark.parametrize("layout,vocab", [("dp4", 50257), ("dp2_tp2", 50304)])
def test_zero3_step_reduces_the_head_gradient_once_behind_the_loss_loop(
        topo, chip, monkeypatch, layout, vocab):
    """The four-chip training cell's own step (``initialize``: ZeRO-3, bf16
    without master weights, bf16 accumulation, micro 2 x gas 4, seq 1024,
    remat "dots", the fused loss over the tied [50257, 2048] embedding; two
    layers for the cell's 24), its engine built on four virtual CPU devices
    and its shardings moved onto the described 2x2 mesh: the optimized
    program has no collective over the data-parallel chips in the body of
    the loss's chunk loop, and the head gradient crosses them in ONE
    reduction a micro-step, in bf16, behind the loop: a reduce-scatter.
    Before PR 34 the accumulated gradient's ``[50257, 512]`` layout was
    propagated into the loop: a float32 ``all-reduce-scatter`` fusion of
    every chunk's product, 32 a step, 160 ms of the cell's 894 (PERF.md,
    PR 34). dp2_tp2 is the
    same step with the embedding vocab-parallel over "model" (a padded
    vocabulary, so that two divide it): that axis stays automatic inside
    the per-shard loss and keeps its own sums in the loop, and the one
    reduction is an all-reduce of each chip's half of the vocabulary (a
    reduce-scatter's operand the partitioner gathers whole over "model"
    first: 206 MB a micro-step)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.parallel.mesh import BATCH_AXES
    from util import collectives, crosses, in_loss_loop, zero3_engine_on_four
    dp, tp = (4, 1) if layout == "dp4" else (2, 2)
    micro, gas, seq = 2, 4, 1024
    rows = micro * gas * dp
    model, cfg = build_model(TransformerConfig(
        vocab_size=vocab, max_seq_len=2048, hidden_size=2048, num_layers=2,
        num_heads=16, mlp_ratio=4, layer_norm_eps=1e-5, activation="gelu",
        pos_embed="learned", tie_embeddings=True, use_bias=True,
        norm="layernorm", remat=True, remat_policy="dots", fused_loss=True))
    with zero3_engine_on_four(
            model, cfg, {"input_ids": np.zeros((rows, seq), np.int32)},
            micro=micro, gas=gas, tp=tp) as engine:
        mesh = Mesh(np.array(topo.devices).reshape(engine.mesh.devices.shape),
                    engine.mesh.axis_names)
        named = lambda x: isinstance(x, NamedSharding)
        move = lambda s: NamedSharding(mesh, s.spec) if named(s) else s
        for name in ("param_shardings", "master_shardings", "grad_shardings",
                     "opt_shardings"):
            setattr(engine, name,
                    jax.tree.map(move, getattr(engine, name), is_leaf=named))
        engine.mesh = engine.mesh_mgr.mesh = engine.zero_policy.mesh = mesh
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        arg = lambda shape, dtype, spec=P(): jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))
        rng = engine.next_rng()
        text = engine._make_train_step().lower(
            jax.tree.map(lambda x: arg(x.shape, x.dtype, x.sharding.spec),
                         engine.state),
            {"input_ids": arg((gas, rows // gas, seq), jnp.int32,
                              P(None, BATCH_AXES))},
            arg(rng.shape, rng.dtype), arg((), jnp.float32)).compile().as_text()

    over_chips = [c for c in collectives(text)
                  if crosses(c[3], mesh, BATCH_AXES)]
    assert not [c for c in over_chips if in_loss_loop(c[2])], over_chips
    kinds = lambda cs: [(c[0], c[1].split("{")[0]) for c in cs]
    if tp == 1:
        head = [c for c in over_chips if "/loss/" in c[2] and "50257" in c[1]]
        assert kinds(head) == [("reduce-scatter", "bf16[50257,512]")], head
        assert "grad_reduce" in head[0][2], head
    else:
        # the compiler combines or renames an all-reduce: found by its shape
        assert kinds(over_chips).count(
            ("all-reduce", f"bf16[{vocab // tp},2048]")) == 1, over_chips
        assert not [c for c in collectives(text) if f"[{vocab}," in c[1]]
