"""OLMoE-class mixture of experts (64 experts, top-8, dropless; here 8 experts,
top-4 at small widths): the one sorted-token function behind the training
module, ``forward_with_cache`` and ``paged_forward``, each held to the plain
reference of ``benchmark/families/olmoe.py`` in float32 on seeded weights.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import harness
from benchmark.drivers.serve import make_params
from benchmark.parity_olmoe import paged_logits
from deepspeed_tpu.models import (TransformerConfig, build_model,
                                  make_moe_loss)
from deepspeed_tpu.models.generation import forward_with_cache, init_cache
from deepspeed_tpu.moe import MoE, top2_gating
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.sharded_moe import compute_capacity, top1_gating
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine

TINY = {"family": "olmoe", "attention_bias": False, "clip_qkv": None,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 48,
        "max_position_embeddings": 256, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 10000, "router_aux_loss_coef": 0.01,
        "tie_word_embeddings": False, "vocab_size": 97}
FAM = harness.load_family("olmoe")
TOL = 1e-4      # float32 both sides: the order of summation only


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    """The session's global mesh (what sharding constraints resolve against,
    and what groups the GShard gating) is whatever the last test of this
    worker left: every test of this file runs on a mesh of one device."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    before = mesh_mod.get_global_mesh()
    mm = MeshManager(devices=jax.devices()[:1])
    mesh_mod.set_global_mesh(mm)
    yield mm
    mesh_mod.set_global_mesh(before)


@pytest.fixture(scope="module")
def tiny():
    model, cfg = build_model(TransformerConfig(
        **FAM.model_kwargs(TINY), dtype=jnp.float32,
        attention_impl="reference"))
    params = make_params(model, cfg, seed=2 ** 31 + 5, dtype=jnp.float32)
    # norm scales away from 1: a reference that dropped q_norm must not pass
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(len(str(p))), a.shape))
        if getattr(p[-1], "key", "") == "scale" else a, params)
    return model, cfg, params


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 97, size=(n,),
                                                dtype=np.int32)


def test_the_config_builds_the_published_tree(tiny):
    model, cfg, params = tiny
    assert cfg.moe_is_dropless and cfg.qk_norm_kind == "projection"
    shapes = jax.tree.map(lambda a: a.shape, params["blocks"])
    assert shapes["moe"] == {
        "gate": {"kernel": (2, 64, 8)},
        "experts": {"gate": {"kernel": (2, 8, 64, 48)},
                    "fc": {"kernel": (2, 8, 64, 48)},
                    "proj": {"kernel": (2, 8, 48, 64)}}}
    assert shapes["q_norm"] == {"scale": (2, 64)} == shapes["k_norm"]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert cfg.num_params() + 2 * 2 * 64 + (2 * 2 + 1) * 64 == n
    assert TransformerConfig(moe_experts=64, moe_k=8, hidden_size=2048,
                             num_heads=16, num_layers=16).moe_is_dropless
    # ... k > 2 needs no flag; a dense or a top-2 config is not dropless
    assert not TransformerConfig(moe_experts=8, moe_k=2).moe_is_dropless
    assert not TransformerConfig(moe_k=8).moe_is_dropless


def test_training_forward_equals_the_reference(tiny):
    model, cfg, params = tiny
    ids = tokens(24)
    with jax.default_matmul_precision("highest"):
        logits, aux = model.apply({"params": params},
                                  {"input_ids": ids[None]})
    want, routing = FAM.reference_logits_and_routing(TINY, params,
                                                     jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(logits[0] - want))) < TOL
    assert np.allclose(np.asarray(FAM.reference_logits(TINY, params,
                                                       jnp.asarray(ids))),
                       np.asarray(want), atol=1e-6)
    # the reference's router is not degenerate here: picks differ by token
    assert len({tuple(r) for r in np.asarray(routing[0][1])}) > 4


def test_forward_with_cache_equals_the_reference(tiny):
    model, cfg, params = tiny
    ids = tokens(30, 1)
    cache = init_cache(cfg, 1, 64)
    with jax.default_matmul_precision("highest"):
        got = []
        for lo, hi in ((0, 20), (20, 21), (21, 30)):
            l, cache = forward_with_cache(cfg, params,
                                          jnp.asarray(ids[None, lo:hi]),
                                          cache)
            got.append(l[0])
    want = FAM.reference_logits(TINY, params, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(jnp.concatenate(got) - want))) < TOL


def test_paged_forward_equals_the_reference(tiny):
    """Chunked prefill whose chunks end in mid-block (block 8, chunk 24,
    prompts of 37 and 50), then decode beside idle lanes."""
    model, cfg, params = tiny
    seqs = [tokens(37 + 6, 2), tokens(50 + 6, 3)]
    with jax.default_matmul_precision("highest"):
        got = paged_logits(cfg, params, seqs, 6, block_size=8, chunk=24,
                           keep=40, interpret=True)
        for s, g in zip(seqs, got):
            want = np.asarray(FAM.reference_logits(TINY, params,
                                                   jnp.asarray(s)))[-40:]
            assert g.shape == want.shape
            assert float(np.max(np.abs(g - want))) < TOL


def test_train_batch_loss_and_gradient_equal_the_references(
        tmp_path, tiny, one_device_mesh):
    model, cfg, params = tiny
    batch = np.stack([tokens(32, 10 + i) for i in range(4)])
    cfg_path = tmp_path / "ds_config.json"
    cfg_path.write_text(json.dumps({
        "train_batch_size": 4, "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
        "optimizer": {"type": "SGD", "params": {"lr": 0.0}}}))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=str(cfg_path),
        model_parameters=jax.tree.map(jnp.copy, params),   # the step donates
        loss_fn=make_moe_loss(TINY["router_aux_loss_coef"]),
        example_batch={"input_ids": batch},
        mesh_manager=one_device_mesh)
    with jax.default_matmul_precision("highest"):
        want = float(FAM.reference_train_loss(TINY, params, batch))
        got = float(engine.train_batch({"input_ids": batch})["loss"])
        assert abs(got - want) < TOL

        def loss(p):
            return make_moe_loss(TINY["router_aux_loss_coef"])(
                model.apply({"params": p}, {"input_ids": batch}),
                {"input_ids": batch})

        g_sys = jax.grad(loss)(params)
        g_ref = jax.grad(lambda p: FAM.reference_train_loss(TINY, p, batch)
                         )(params)
    flat_s, flat_r = jax.tree.leaves(g_sys), jax.tree.leaves(g_ref)
    for a, b in zip(flat_s, flat_r):
        assert float(jnp.max(jnp.abs(a - b))) < TOL
    # the experts and the router do get a gradient
    assert float(jnp.abs(g_sys["blocks"]["moe"]["gate"]["kernel"]).max()) > 0
    assert float(jnp.abs(
        g_sys["blocks"]["moe"]["experts"]["proj"]["kernel"]).max()) > 0


def test_every_token_gets_exactly_k_experts_under_a_skewed_router():
    T, H, E, k = 40, 16, 8, 4
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, H))
    router = jax.random.normal(jax.random.fold_in(key, 1), (H, E)) * 0.1
    # every token's best pick is expert 3, by a wide margin
    router = router.at[:, 3].set(0.0)
    x = x.at[:, 0].set(4.0)
    router = router.at[0, 3].set(5.0)
    experts = {n: {"kernel": jax.random.normal(
        jax.random.fold_in(key, i), (E,) + s) * 0.2}
        for i, (n, s) in enumerate((("gate", (H, 12)), ("fc", (H, 12)),
                                    ("proj", (12, H))), 2)}
    y, r = dropless.dropless_moe(x, router, experts, k=k, renorm=False,
                                 act=jax.nn.silu)
    picks = np.asarray(r.experts)
    assert picks.shape == (T, k) and (picks[:, 0] == 3).all()
    assert all(len(set(row)) == k for row in picks)        # k distinct
    assert int(r.group_sizes.sum()) == T * k               # nothing dropped
    assert int(r.group_sizes[3]) == T                      # no capacity
    # ... and the output is the masked sum over all experts
    want = FAM.reference_moe({"gate": {"kernel": router}, "experts": experts},
                             x, k, False)[0]
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    # renormalised weights sum to one
    _, rn = dropless.dropless_moe(x, router, experts, k=k, renorm=True,
                                  act=jax.nn.silu)
    assert np.allclose(np.asarray(rn.weights).sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("rows,sizes", [
    (128, [16] * 8), (40, [0, 9, 0, 20, 1, 0, 10, 0]), (300, [300] + [0] * 7)],
    ids=["even", "ragged-with-idle-experts", "one-expert"])
def test_the_tpu_kernel_interpreted_equals_ragged_dot(rows, sizes):
    """``grouped_matmul``'s TPU forward (megablox, here interpreted) against
    its CPU forward (``jax.lax.ragged_dot``), on row counts that are and are
    not whole tiles; its backward is ``ragged_dot``'s on both."""
    key = jax.random.PRNGKey(rows)
    x = jax.random.normal(key, (rows, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (8, 64, 48)) * 0.1
    g = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(x, w, g)
    got = dropless.grouped_matmul(x, w, g, True)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(dropless.grouped_matmul(x, w, g) - want))) \
        == 0.0
    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(f(x, w)))
    g_want = jax.grad(loss(lambda x, w: jax.lax.ragged_dot(x, w, g)),
                      argnums=(0, 1))(x, w)
    g_got = jax.grad(loss(lambda x, w: dropless.grouped_matmul(x, w, g, True)),
                     argnums=(0, 1))(x, w)
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * (
            1.0 + float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("k", [1, 2])
def test_the_gshard_path_is_unchanged_bit_for_bit(k):
    """``MoE(k in {1, 2})`` still is gate -> one-hot dispatch -> vmapped
    experts -> combine over a capacity, to the last bit: the same arithmetic
    written out here from the gating functions."""
    B, S, H, E = 2, 12, 16, 4
    moe = MoE(hidden_size=H, num_experts=E, k=k, capacity_factor=1.5,
              eval_capacity_factor=1.5, dtype=jnp.float32)
    assert not moe.is_dropless
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H))
    params = moe.init(jax.random.PRNGKey(2), x)["params"]
    assert set(params) == {"gate", "experts"}
    assert params["experts"]["fc"]["kernel"].shape == (E, H, 4 * H)
    y, aux = moe.apply({"params": params}, x)
    tokens_ = x.reshape(B * S, H)
    C = compute_capacity(B * S, E, 1.5, k, 4)
    gating = top1_gating if k == 1 else top2_gating
    aux_w, combine, dispatch, _ = gating(tokens_ @ params["gate"]["kernel"],
                                         1.5, 4, rng=None, capacity=C)
    q = jnp.einsum("tec,th->ech", dispatch.astype(jnp.float32), tokens_)
    ex = params["experts"]
    h = jax.nn.gelu(jnp.einsum("ech,ehm->ecm", q, ex["fc"]["kernel"])
                    + ex["fc"]["bias"][:, None])
    out = jnp.einsum("ecm,emh->ech", h, ex["proj"]["kernel"]) \
        + ex["proj"]["bias"][:, None]
    want = jnp.einsum("tec,ech->th", combine, out).reshape(B, S, H)
    assert np.allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    assert float(aux) == float(aux_w)


def test_dropless_on_an_expert_axis_raises_a_clear_error():
    from deepspeed_tpu.parallel import mesh as mesh_mod
    mm = MeshManager(ep_size=2, devices=jax.devices()[:2])
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(mm)
    try:
        moe = MoE(hidden_size=16, num_experts=8, k=4, dtype=jnp.float32)
        with pytest.raises(NotImplementedError, match="expert-parallel"):
            moe.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))
    finally:
        mesh_mod.set_global_mesh(before)


def test_no_capacity_tensor_in_the_serving_programs():
    """The decode and prefill programs as lowered for the TPU (on the CPU jax
    itself expands ``ragged_dot`` into a masked dot over all experts; the
    chip's compiler has a grouped-matmul kernel for it): the expert matmuls
    are ``ragged_dot``s over ``tokens x k`` rows, and no array has both a
    token axis and an expert axis beside a third (a ``[tokens, experts,
    capacity]`` one-hot, a queue padded to a capacity per expert). 12 experts
    and 5 lanes here, so that no other axis has an expert's or a row's length."""
    import re
    model, cfg = build_model(TransformerConfig(
        **FAM.model_kwargs(dict(TINY, num_experts=12)), dtype=jnp.float32,
        attention_impl="reference"))
    params = make_params(model, cfg, seed=7, dtype=jnp.float32)
    srv = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 20, "max_batch": 5,
        "max_blocks_per_seq": 4, "prefill_chunk_tokens": 32})
    B, T, E, k = 5, 32, 12, 4
    progs = {
        "decode": (B, srv._decode_fn, (srv.params, srv.pools,
                                       srv._lanes.buf, srv._dec_out,
                                       srv._pre_out)),
        "prefill": (T, srv._prefill_fn, (srv.params, srv.pools, np.zeros(
            (srv._layout.prefill_words(T),), np.int32)))}
    for name, (tok, fn, args) in progs.items():
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert len(re.findall(r"ragged_dot", text)) >= 3, name
        shapes = {tuple(int(d) for d in s.split("x"))
                  for s in re.findall(r"tensor<([0-9x]+)x[a-z]", text)}
        assert (tok * k, 64) in shapes and (tok * k, 48) in shapes, name
        assert (E, 64, 48) in {d[-3:] for d in shapes}, name   # the kernels
        for dims in shapes:
            # an expert axis beside a token (or row) axis and a third of
            # any length: a one-hot dispatch, a queue with a capacity
            wide = [d for d in dims if d > 1]
            assert not (len(wide) >= 3 and E in wide
                        and (tok in wide or tok * k in wide)), (name, dims)


def test_a_dense_configs_programs_and_counters_are_what_they_were():
    """The fence, as far as the CPU can hold it: for a config that is not a
    dropless mixture ``step_programs`` hands out the two programs under the
    one signature (params, pools, the call's one int32 buffer; the decode
    program since PR 40 the two token vectors it reads on the device), each
    returning the tokens ``[lanes]`` and the pools and nothing else; the
    engine keeps no ``moe.`` counter and no pending counts; and what it
    counts is the list it counted before this file, and since PR 31 the two
    ``step_inputs.`` counters."""
    import inspect
    from deepspeed_tpu.serving import engine as eng
    model, cfg = build_model(TransformerConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=256, norm="rmsnorm", gated_mlp=True, activation="silu",
        pos_embed="rotary", use_bias=False, tie_embeddings=False,
        dtype=jnp.float32, attention_impl="reference"))
    assert not cfg.moe_is_dropless
    decode, prefill = eng.step_programs(cfg, 16, 4)
    assert decode.__name__ == "_decode" and prefill.__name__ == "_prefill"
    assert list(inspect.signature(decode).parameters) == [
        "params", "pools", "step_in", "prev", "first"]
    assert list(inspect.signature(prefill).parameters) == [
        "params", "pools", "step_in"]
    params = make_params(model, cfg, seed=3, dtype=jnp.float32)
    srv = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 20, "max_batch": 5,
        "max_blocks_per_seq": 4, "prefill_chunk_tokens": 32})
    B, T, layout = 5, 32, eng.StepLayout(4)
    assert srv.nbk == 4 and srv._lanes.buf.shape == (layout.decode_words(B),)
    assert srv._dec_out.shape == (eng.token_words(cfg, B),) == (B,)
    assert srv._pre_out.shape == (eng.token_words(cfg, 1),) == (1,)
    tok, pools = jax.eval_shape(decode, srv.params, srv.pools,
                                srv._lanes.buf, srv._dec_out, srv._pre_out)
    assert tok.shape == (B,) and set(pools) == set(srv.pools)
    tok, pools = jax.eval_shape(
        prefill, srv.params, srv.pools,
        np.zeros((layout.prefill_words(T),), np.int32))
    assert tok.shape == (1,) and set(pools) == set(srv.pools)
    assert eng._COUNTERS == (
        "completed", "failed", "timeout", "tokens_generated",
        "prefill_tokens", "prefix_hit_tokens", "preempted", "steps",
        "steps_with_queue", "queue_len_sum", "lane_sum",
        "admit_blocked.no_lane", "admit_blocked.no_blocks",
        "admit_blocked.prefilling", "compiles", "kv.held_blocks_sum",
        "kv.blocks_reserved_sum", "kv.tokens_written_sum",
        "prefix.prompt_tokens", "paged.live_pages_sum",
        "paged.table_pages_sum", "paged.window_pages_sum",
        "paged.chunk_live_pages_sum",
        "paged.chunk_table_pages_sum", "paged.chunk_turns_sum",
        "paged.chunk_key_tiles_sum", "paged.chunk_key_tiles_live_sum",
        "step_inputs.transfers_sum",
        "step_inputs.lane_rows_written_sum", "decode_ahead.launched",
        "decode_ahead.device_lane_tokens_sum",
        "decode_ahead.wasted_lane_tokens", "decode_ahead.retired_unread",
        "mixed.calls", "mixed.lane_rows_sum",       # since PR 57
        "prefill_rows")                             # since PR 60
    # ... and asked for, the third: a chunk and the lanes in one program,
    # under the decode program's signature, returning both kinds' tokens
    _, _, mixed = eng.step_programs(cfg, 16, 4, mixed=True)
    assert mixed.__name__ == "_mixed"
    assert inspect.signature(mixed) == inspect.signature(decode)
    (tok, first), pools = jax.eval_shape(
        mixed, srv.params, srv.pools, np.zeros(
            (layout.prefill_words(T) + layout.decode_words(B),), np.int32),
        srv._dec_out, srv._pre_out)
    assert tok.shape == (B,) and first.shape == (1,) \
        and set(pools) == set(srv.pools)
    srv.submit(list(tokens(40, 5)), max_new_tokens=4)
    srv.run_until_idle()
    assert srv.stats["completed"] == 1
    assert not [k for k in srv.stats if k.startswith("moe.")]
    assert not srv._moe_pending
    # ... and the dropless pair has the same two signatures
    moe_model, moe_cfg = build_model(TransformerConfig(
        **FAM.model_kwargs(TINY), dtype=jnp.float32,
        attention_impl="reference"))
    d2, p2 = eng.step_programs(moe_cfg, 16, 4)
    assert inspect.signature(d2) == inspect.signature(decode)
    assert inspect.signature(p2) == inspect.signature(prefill)
