"""One decoder, two caches: ``models.generation.decoder_forward`` over the
dense cache (``forward_with_cache``, what ``generate()`` runs) and over the
paged pool (``serving.model_runner.paged_forward``, what ``serve()`` runs)
gives the same logits at every position, one case a feature the shared
layer branches on. float32 on the CPU at tiny widths: the two differ in
where a row is written and what gathers it, not in the arithmetic.

The paged side takes the prompt as the serving loop does: two uneven chunks
that both end in mid-block (the second padded to the first's shape), then
one-token decode steps with an idle lane beside the live one. The dense side
takes the whole prompt at once, then the same tokens one at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import quantize_weights_int8
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.generation import forward_with_cache, init_cache
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import paged_forward

BS, NBK, BLOCKS = 8, 5, 12          # slots a block, blocks a lane, pool blocks
PROMPT, CHUNK, STEPS = 21, 13, 3    # chunks of 13 and of 8 padded to 13
VOCAB = 64

_ROTARY = dict(pos_embed="rotary", rotary_interleaved=False)
_SWIGLU = dict(norm="rmsnorm", gated_mlp=True, activation="silu",
               use_bias=False, mlp_dim_override=48)
FEATURES = {
    "learned_positions_tied_head": dict(),
    "rotary_rmsnorm_swiglu_gqa_untied_head": dict(
        **_ROTARY, **_SWIGLU, num_kv_heads=2, tie_embeddings=False),
    "partial_rotary_dim": dict(**_ROTARY, rotary_dim=4),
    "interleaved_rotary": dict(pos_embed="rotary", rotary_interleaved=True),
    "alibi": dict(pos_embed="alibi"),
    "parallel_residual_shared_ln": dict(**_ROTARY, parallel_residual=True),
    "parallel_residual_dual_ln": dict(**_ROTARY, parallel_residual=True,
                                      parallel_residual_dual_ln=True),
    "mixed_layer_windows": dict(layer_windows=(0, 5)),
    "uniform_window": dict(**_ROTARY, layer_windows=(6, 6)),
    "sandwich_norms_and_softcaps": dict(
        **_ROTARY, norm="rmsnorm", post_block_norms=True, attn_softcap=20.0,
        final_logit_softcap=15.0),
    "embed_ln": dict(pos_embed="alibi", embed_ln=True),
    "embed_scale": dict(**_ROTARY, embed_scale=32 ** 0.5),
    "qk_norm_per_head": dict(**_ROTARY, qk_norm=True),
    "qk_norm_projection": dict(**_ROTARY, qk_norm="projection"),
    "moe_gshard_top2": dict(moe_experts=4, moe_k=2),
    "moe_dropless_top8": dict(**_ROTARY, **_SWIGLU, moe_experts=16, moe_k=8,
                              moe_dropless=True, moe_norm_topk=False),
    "int8_kv": dict(**_ROTARY),
    # grouped-query models (PR 44): the pool holds the KV heads, the dense
    # cache a row a query head; the int8 tier's scales follow each; ALiBi
    # slopes stay a query head's own; one KV head for all is multi-query
    "gqa_int8_kv": dict(**_ROTARY, num_kv_heads=2),
    "gqa_alibi_softcap_window": dict(pos_embed="alibi", num_kv_heads=2,
                                     attn_softcap=20.0,
                                     layer_windows=(0, 5)),
    "multi_query": dict(**_ROTARY, num_kv_heads=1),
    "int8_weights_per_channel": dict(**_ROTARY, tie_embeddings=False),
    # K-EXAONE's layer: a chip's share of the experts (2 of 16 held), a
    # sigmoid router with a selection bias, renormalised and scaled picks, a
    # shared expert, a leading dense layer, no input norms, rotary on the
    # window layers only
    "share_of_sigmoid_mixture_behind_a_dense_layer": dict(
        **_ROTARY, **dict(_SWIGLU, mlp_dim_override=24), num_layers=5,
        num_kv_heads=2, tie_embeddings=False, qk_norm=True, pre_norm=False,
        post_block_norms=True, layer_windows=(6, 6, 6, 0, 6),
        layer_rope=(True, True, True, False, True), dense_layers=1,
        dense_mlp_dim=80, moe_experts=16, moe_k=4, moe_held=(4, 2),
        moe_dropless=True, moe_scores="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.5, moe_shared_dim=24),
    # Keye-VL-2.0's attention: a learned indexer picks each query's 6 keys
    # (DeepSeek-Sparse-Attention's index score), alone and beside a window
    "indexer_selects_keys": dict(**_ROTARY, num_kv_heads=2, qk_norm=True,
                                 index_heads=2, index_head_dim=8,
                                 index_topk=6),
    "indexer_beside_a_window": dict(**_ROTARY, layer_windows=(0, 9),
                                    index_heads=3, index_head_dim=4,
                                    index_topk=5),
}


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    """Constraints (and the GShard gating's groups) resolve against the
    global mesh, which is whatever the worker's last test left."""
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


@pytest.mark.parametrize("feature", sorted(FEATURES))
def test_dense_and_paged_forward_agree(feature):
    model, cfg = build_model("gpt2-tiny", **dict(dict(
        hidden_size=32, num_layers=2, num_heads=4, vocab_size=VOCAB,
        max_seq_len=64, attention_impl="reference", dtype=jnp.float32),
        **FEATURES[feature]))
    params = model.init(jax.random.PRNGKey(3),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    if cfg.moe_select_bias:         # drawn zero: give it something to select
        gate = params["blocks"]["moe"]["gate"]
        gate["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                               gate["bias"].shape)
    if feature == "int8_weights_per_channel":
        params = quantize_weights_int8(params)
    kv_dtype = jnp.int8 if feature.endswith("int8_kv") else jnp.float32
    counting = cfg.moe_is_dropless
    ids = np.random.default_rng(7).integers(
        1, VOCAB, size=(1, PROMPT + STEPS)).astype(np.int32)

    # ---- the dense cache: the prompt at once, then a token a call
    dense = jax.jit(lambda ids, cache: forward_with_cache(
        cfg, params, ids, cache))
    cache = init_cache(cfg, 1, 32, kv_dtype)
    logits, cache = dense(ids[:, :PROMPT], cache)
    want = [np.asarray(logits)]
    for t in range(PROMPT, PROMPT + STEPS):
        logits, cache = dense(ids[:, t:t + 1], cache)
        want.append(np.asarray(logits))
    want = np.concatenate(want, axis=1)[0]             # [PROMPT + STEPS, V]
    assert int(cache["pos"]) == PROMPT + STEPS

    # ---- the paged pool: two chunks, then decode steps beside an idle lane
    paged = jax.jit(lambda ids, pools, bt, q0, ctx: paged_forward(
        cfg, params, ids, pools, bt, q0, ctx, BS, expert_counts=counting))
    table = np.full((1, NBK), NULL_BLOCK, np.int32)
    table[0, :4] = (7, 2, 9, 4)                        # 24 of 32 slots used
    pools = init_pool(cfg, BLOCKS, BS, kv_dtype)
    # the pool holds the model's KV heads, generate()'s cache every query head
    assert pools["k"].shape == (cfg.num_layers, cfg.kv_heads, BLOCKS * BS,
                                cfg.head_dim)
    assert cache["k"].shape[2] == cfg.num_heads
    got, counted = [], []

    def call(tokens, bt, q0, ctx, real):
        nonlocal pools
        out = paged(jnp.asarray(tokens), pools, jnp.asarray(bt),
                    jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32))
        pools = out[1]
        got.append(np.asarray(out[0])[0, :real])
        if counting:
            counted.append((np.asarray(out[2]), real))

    call(ids[:, :CHUNK], table, [0], [CHUNK], CHUNK)
    second = np.zeros((1, CHUNK), np.int32)            # one compiled shape
    second[0, :PROMPT - CHUNK] = ids[0, CHUNK:PROMPT]
    call(second, table, [CHUNK], [PROMPT], PROMPT - CHUNK)
    lanes = np.concatenate([table, np.full((1, NBK), NULL_BLOCK, np.int32)])
    for t in range(PROMPT, PROMPT + STEPS):
        call(np.asarray([[ids[0, t]], [0]], np.int32), lanes, [t, 0],
             [t + 1, 1], 1)
    got = np.concatenate(got, axis=0)

    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for counts, real in counted:
        # every real token reaches moe_k experts in every sparse layer, held
        # here or not; padding and the idle lane are routed like any row,
        # and not counted
        assert counts.shape == (cfg.sparse_layers, cfg.moe_experts)
        assert (counts.sum(axis=1) == real * cfg.moe_k).all(), counts
    assert len(counted) == (2 + STEPS if counting else 0)
