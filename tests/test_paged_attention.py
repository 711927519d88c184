"""Pallas paged-attention kernel: parity vs a dense numpy oracle.

The kernel copies the K/V pages a sequence holds through its block table,
several a turn of a loop inside one program a sequence (serving decode
path); the oracle materializes each sequence's logical K/V by following the
table on the host and runs dense masked attention. Interpret mode on CPU —
the same kernel runs compiled on TPU. Covers the acceptance regimes: padding
(ragged context lengths, dead table entries), ALiBi, softcap, sliding
window, stacked layer pools, the int8 tier, and the loop's edges in each.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference)
from deepspeed_tpu.quant_format import kv_quantize


def _oracle(q, k_pool, v_pool, bt, lens, *, window=0, slopes=None,
            softcap=0.0):
    """Dense numpy oracle: gather via table, mask, f32 softmax."""
    B, nh, T, hd = q.shape
    bs = k_pool.shape[2]
    nbk = bt.shape[1]
    out = np.zeros((B, nh, T, hd), np.float32)
    for b in range(B):
        k = np.concatenate([k_pool[:, bt[b, j]] for j in range(nbk)],
                           axis=1)                     # [nh, nbk*bs, hd]
        v = np.concatenate([v_pool[:, bt[b, j]] for j in range(nbk)], axis=1)
        q_abs = np.arange(lens[b] - T, lens[b])        # [T]
        k_pos = np.arange(nbk * bs)
        s = np.einsum("htd,hkd->htk", q[b].astype(np.float32),
                      k.astype(np.float32)) / np.sqrt(hd)
        if softcap:
            s = np.tanh(s / softcap) * softcap
        if slopes is not None:
            s = s + slopes[:, None, None] * (
                k_pos[None, None, :] - q_abs[None, :, None])
        mask = k_pos[None, :] <= q_abs[:, None]
        if window > 0:
            mask &= q_abs[:, None] - k_pos[None, :] < window
        s = np.where(mask[None], s, -1e30)
        p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
        out[b] = np.einsum("htk,hkd->htd", p, v.astype(np.float32))
    return out


def _data(B=3, nh=4, hd=64, bs=16, num_blocks=32, nbk=8, seed=0):
    """Random pool + a random (valid, non-overlapping) block assignment."""
    rng = np.random.default_rng(seed)
    k_pool = rng.standard_normal((nh, num_blocks, bs, hd)).astype(np.float32)
    v_pool = rng.standard_normal((nh, num_blocks, bs, hd)).astype(np.float32)
    # distinct physical blocks per (b, j); block 0 reserved as null
    perm = rng.permutation(num_blocks - 1)[:B * nbk] + 1
    bt = perm.reshape(B, nbk).astype(np.int32)
    lens = rng.integers(1, nbk * bs + 1, size=B).astype(np.int32)
    q = rng.standard_normal((B, nh, 1, hd)).astype(np.float32)
    return q, k_pool, v_pool, bt, lens


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_parity_ragged_lengths(seed):
    q, kp, vp, bt, lens = _data(seed=seed)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens), interpret=True)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)


def test_paged_parity_alibi():
    q, kp, vp, bt, lens = _data()
    slopes = np.asarray([2.0 ** -(i + 1) for i in range(4)], np.float32)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens),
                          alibi_slopes=jnp.asarray(slopes), interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, slopes=slopes),
        rtol=2e-5, atol=2e-5)


def test_paged_parity_softcap():
    q, kp, vp, bt, lens = _data(seed=2)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens), softcap=30.0,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, softcap=30.0),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 50])
def test_paged_parity_window(window):
    q, kp, vp, bt, lens = _data(seed=3)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens),
                          window=jnp.asarray(window, jnp.int32),
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, window=window),
        rtol=2e-5, atol=2e-5)


def test_paged_stacked_layer_pool():
    """layer_idx form: blocks picked straight out of the [L, ...] pool."""
    L = 3
    q, kp, vp, bt, lens = _data(B=2, nbk=4)
    kpl = np.stack([kp * (l + 1) for l in range(L)])
    vpl = np.stack([vp * 0.5 * (l + 1) for l in range(L)])
    for li in range(L):
        out = paged_attention(jnp.asarray(q), jnp.asarray(kpl),
                              jnp.asarray(vpl), jnp.asarray(bt),
                              jnp.asarray(lens),
                              layer_idx=jnp.asarray(li, jnp.int32),
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(q, kpl[li], vpl[li], bt, lens),
            rtol=2e-5, atol=2e-5)


def test_paged_reference_matches_kernel_and_serves_prefill():
    """The jnp reference (the CPU/serving fallback) agrees with the numpy
    oracle for T=1 AND for the prefill regime (T>1), and so does the kernel
    since PR 37: T query rows a lane ending at lens[b], an odd T padded to
    whole sublanes inside the call."""
    q, kp, vp, bt, lens = _data(seed=4)
    ref = paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(ref), _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)
    # prefill: 5 queries ending at lens[b]
    rng = np.random.default_rng(9)
    B, nh, _, hd = q.shape
    lens5 = np.maximum(lens, 5)
    q5 = rng.standard_normal((B, nh, 5, hd)).astype(np.float32)
    args = (jnp.asarray(q5), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens5))
    want = _oracle(q5, kp, vp, bt, lens5)
    np.testing.assert_allclose(np.asarray(paged_attention_reference(*args)),
                               want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(paged_attention(*args, interpret=True)), want, rtol=2e-5,
        atol=2e-5)


def test_router_dispatch(monkeypatch):
    """ops.attention.paged_attention: the kernel for a decode token AND a
    prefill chunk under interpret (one pallas_call either way, the same
    numerics as the reference), the reference where it is asked for or no
    TPU is there; on a TPU ``untileable`` alone routes, from the shapes,
    and says why once."""
    from deepspeed_tpu.ops import attention as ops
    from deepspeed_tpu.ops.pallas.paged_attention import untileable
    q, kp, vp, bt, lens = _data(seed=5)
    rng = np.random.default_rng(5)
    q4 = rng.standard_normal(q.shape[:2] + (4, q.shape[3])).astype(np.float32)
    lens4 = np.maximum(lens, 4)
    for qq, ll in ((q, lens), (q4, lens4)):
        args = (jnp.asarray(qq), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(ll))
        want = _oracle(qq, kp, vp, bt, ll)
        for kw, kernels in ((dict(interpret=True), 1), ({}, 0),
                            (dict(interpret=True, impl="reference"), 0)):
            fn = lambda *a: ops.paged_attention(*a, **kw)
            assert str(jax.make_jaxpr(fn)(*args)).count(
                "pallas_call") == kernels, kw
            np.testing.assert_allclose(np.asarray(fn(*args)), want,
                                       rtol=2e-5, atol=2e-5)
    # the routing as a TPU sees it: shapes in, a path and a reason out
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = lambda T, hd, bs=32, quant=False: ops.paged_attention_path(
        (1, 4, T, hd), (2, 4, 16, bs, hd), stacked=True, quant=quant)
    assert path(1, 128) == path(256, 128) == path(1, 64) == ("kernel", None)
    assert path(256, 128, quant=True) == ("kernel", None)
    for shape, why in (((256, 64), "128 lanes"), ((2048, 128), "chunk"),
                       ((1, 128, 12), "block_size 12"),
                       ((32, 128, 16, True), "int8")):
        got = path(*shape)
        assert got[0] == "reference" and why in got[1], got
    assert ops.paged_attention_path(
        (1, 4, 32, 128), (2, 4, 16, 32, 128), stacked=True, quant=False,
        impl="reference") == ("reference", None)
    # under the interpreter nothing is refused (no tile to fit)
    assert untileable((1, 4, 5, 64), (4, 16, 12, 64), stacked=False,
                      quant=False, interpret=True) is None


# ---------------------------------------------------------------------------
# int8 KV tier (round 17): the kernel copies int8 pages + per-row scales and
# dequantizes IN VMEM — parity vs the numpy oracle running on the
# dequantized pools must be as tight as the f32 tier's, in every routed
# regime, because the in-kernel dequant reconstructs the identical values.
# ---------------------------------------------------------------------------

def _int8_pools(kp, vp):
    """Quantize pools to the serving format: int8 values + one f32 scale
    per (head, block, slot) row; returns the exact dequantized floats the
    oracle attends over."""
    (kq, ks), (vq, vs) = kv_quantize(jnp.asarray(kp)), kv_quantize(
        jnp.asarray(vp))
    kd = np.asarray(kq, np.float32) * np.asarray(ks)
    vd = np.asarray(vq, np.float32) * np.asarray(vs)
    return kq, ks, vq, vs, kd, vd


@pytest.mark.parametrize("regime", ["plain", "alibi", "softcap", "window16",
                                    "window50"])
def test_paged_int8_parity_all_regimes(regime):
    q, kp, vp, bt, lens = _data(seed=6)
    kq, ks, vq, vs, kd, vd = _int8_pools(kp, vp)
    kw, okw = {}, {}
    if regime == "alibi":
        slopes = np.asarray([2.0 ** -(i + 1) for i in range(4)], np.float32)
        kw["alibi_slopes"] = jnp.asarray(slopes)
        okw["slopes"] = slopes
    elif regime == "softcap":
        kw["softcap"] = okw["softcap"] = 30.0
    elif regime.startswith("window"):
        w = int(regime[len("window"):])
        kw["window"] = jnp.asarray(w, jnp.int32)
        okw["window"] = w
    args = (jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(lens))
    out = paged_attention(*args, k_scale=ks, v_scale=vs, interpret=True,
                          **kw)
    ref = paged_attention_reference(*args, k_scale=ks, v_scale=vs, **kw)
    want = _oracle(q, kd, vd, bt, lens, **okw)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-5)


def test_paged_int8_stacked_layer_pool():
    """int8 + layer_idx: per-layer scale rows are copied through the SAME
    block table as the values — each layer dequantizes with its own rows."""
    L = 3
    q, kp, vp, bt, lens = _data(B=2, nbk=4, seed=7)
    kpl = np.stack([kp * (l + 1) for l in range(L)])
    vpl = np.stack([vp * 0.5 * (l + 1) for l in range(L)])
    kq, ks, vq, vs, kd, vd = _int8_pools(kpl, vpl)
    for li in range(L):
        out = paged_attention(jnp.asarray(q), kq, vq, jnp.asarray(bt),
                              jnp.asarray(lens), k_scale=ks, v_scale=vs,
                              layer_idx=jnp.asarray(li, jnp.int32),
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(q, kd[li], vd[li], bt, lens),
            rtol=2e-5, atol=2e-5)


def test_paged_int8_guards():
    """int8 pools without scales (and scales without int8 pools) raise —
    a silent garbage read is the failure mode these guard against."""
    q, kp, vp, bt, lens = _data(B=1, nbk=2, seed=8)
    kq, ks, vq, vs, _, _ = _int8_pools(kp, vp)
    with pytest.raises(ValueError, match="scale"):
        paged_attention(jnp.asarray(q), kq, vq, jnp.asarray(bt),
                        jnp.asarray(lens), interpret=True)
    with pytest.raises(ValueError, match="int8"):
        paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                        jnp.asarray(bt), jnp.asarray(lens), k_scale=ks,
                        v_scale=vs, interpret=True)


# ---------------------------------------------------------------------------
# The loop's edges (PR 28): the kernel walks grid=(lanes, head groups) and,
# inside a program, the groups of P pages its lane holds, the next group
# (or the next lane's first) copied while one is computed. Each edge of
# that loop, in every regime the kernel has. One small shape throughout
# (4 lanes, tables of 6 pages of 8 slots, heads of 128: narrower heads take
# the grid kernel, which the tests above hold), from which the program
# derives P = 4: two groups, the second half a group (the table is no
# multiple of P); one jitted call a regime, the edges only change what it is
# fed.
# ---------------------------------------------------------------------------

_EB, _ENH, _EHD, _EBS, _ENB, _ENBK, _EP = 4, 2, 128, 8, 40, 6, 4
_ESLOPES = np.asarray([0.5, 0.125], np.float32)
_REGIMES = {
    "plain": {}, "stacked": {"stacked": True}, "window": {"window": 12},
    "alibi": {"slopes": _ESLOPES}, "softcap": {"softcap": 30.0},
    "int8": {"quant": True}}
_EDGES = {
    # an idle lane first, one between live ones, and a live one after it
    # that nobody started a copy for
    "idle_lane": [0, 29, 0, 41],
    "exactly_one_group": [_EP * _EBS, 17, _EP * _EBS, 9],
    "one_token_past_a_group": [_EP * _EBS + 1, _EP * _EBS, _EP * _EBS + 1, 1],
    "one_token": [1, 1, _ENBK * _EBS, 1],
    "full_table": [_ENBK * _EBS, _ENBK * _EBS, 7, _ENBK * _EBS],
    "table_no_multiple_of_group": [35, 47, 5, 40],
    "shared_pages": [30, 27, 44, 12]}


def _edge_call(regime, fresh=False):
    """The jitted kernel call of one regime, built once (the edges share
    its shapes); ``fresh`` traces a new one (under a forced P)."""
    r = _REGIMES[regime]
    kw = {}
    if "slopes" in r:
        kw["alibi_slopes"] = jnp.asarray(r["slopes"])
    if "softcap" in r:
        kw["softcap"] = r["softcap"]

    def call(q, kp, vp, bt, lens, ks, vs):
        if "window" in r:
            kw["window"] = jnp.asarray(r["window"], jnp.int32)
        if r.get("stacked"):
            kw["layer_idx"] = jnp.asarray(1, jnp.int32)
        if r.get("quant"):
            kw["k_scale"], kw["v_scale"] = ks, vs
        return paged_attention(q, kp, vp, bt, lens, interpret=True, **kw)

    if fresh:
        return jax.jit(call)
    if regime not in _edge_call.cache:
        _edge_call.cache[regime] = jax.jit(call)
    return _edge_call.cache[regime]


_edge_call.cache = {}


def _edge_case(regime, lens, shared=False, seed=11):
    """Inputs of one edge in one regime, and the oracle's answer (an idle
    lane's row is zeros by the kernel's contract)."""
    r = _REGIMES[regime]
    q, kp, vp, bt, _ = _data(B=_EB, nh=_ENH, hd=_EHD, bs=_EBS,
                             num_blocks=_ENB, nbk=_ENBK, seed=seed)
    lens = np.asarray(lens, np.int32)
    if shared:
        bt[1, :3] = bt[0, :3]            # a shared prefix of three pages
    ks = vs = jnp.zeros((1,), jnp.float32)
    okp, ovp = kp, vp
    if r.get("quant"):
        kp, ks, vp, vs, okp, ovp = _int8_pools(kp, vp)
    if r.get("stacked"):
        okp, ovp = kp * 2.0, vp * 0.5
        kp, vp = np.stack([kp, okp]), np.stack([vp, ovp])
    want = _oracle(q, okp, ovp, bt, np.maximum(lens, 1),
                   window=r.get("window", 0), slopes=r.get("slopes"),
                   softcap=r.get("softcap", 0.0))
    want[lens == 0] = 0.0
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens), ks, vs)
    return args, want


def test_edge_shape_derives_two_ragged_groups():
    """The edge cases below mean what their names say only while the
    program derives 4 pages a group from their shape."""
    from deepspeed_tpu.ops.pallas.paged_attention import (_head_group,
                                                          _pages_per_group)
    for itemsize, quant in ((4, False), (1, True)):
        hg = _head_group(_ENH, _EBS, _EHD, itemsize)
        assert hg == _ENH
        assert _pages_per_group(hg, _EBS, _EHD, itemsize, _ENBK,
                                quant) == _EP


@pytest.mark.parametrize("hd,axes", [(128, 2), (256, 2), (64, 3), (96, 3)])
def test_kernel_by_head_width(hd, axes):
    """Heads of whole 128-lane tiles take the loop kernel, whose grid is
    lanes x head groups; narrower ones (the chip's compiler refuses a
    kernel's own copy of part of a padded row) the grid kernel, with the
    table's length as a third axis."""
    q, kp, vp, bt, lens = _data(B=2, nh=2, hd=hd, bs=8, num_blocks=12, nbk=4)
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(*a, interpret=True))(
        q, kp, vp, bt, lens)
    grids = [e.params["grid_mapping"].grid for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(grids) == 1 and len(grids[0]) == axes, grids


@pytest.mark.parametrize("edge", list(_EDGES))
@pytest.mark.parametrize("regime", list(_REGIMES))
def test_paged_loop_edges(regime, edge):
    args, want = _edge_case(regime, _EDGES[edge], shared=edge == "shared_pages")
    out = _edge_call(regime)(*args)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages", [1, 2])
@pytest.mark.parametrize("regime", ["plain", "window", "int8"])
def test_paged_loop_alternates_slots_over_many_groups(monkeypatch, regime,
                                                      pages):
    """Fewer pages a group than the program would pick: three to six turns
    of the loop a lane, so both buffer slots are reused, a window's first
    group lies past the table's start, and a lane's last turn starts the
    next lane's copy into the slot the lane after that continues from."""
    from deepspeed_tpu.ops.pallas import paged_attention as mod
    monkeypatch.setattr(mod, "_pages_per_group", lambda *a, **k: pages)
    args, want = _edge_case(regime, [41, 0, 48, 19], seed=12)
    out = _edge_call(regime, fresh=True)(*args)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("regime", ["plain", "int8", "chunk", "chunk-int8",
                                    "grouped", "grouped-chunk"])
def test_paged_loop_under_the_tpu_interpreter(regime):
    """The same kernel under the TPU interpreter, which keeps semaphores
    and copies apart from the compute as the chip does: every buffer it
    allocates starts as NaN (a page the loop skipped must not reach the
    output through a zero probability) and no copy races a read. A chunk's
    programs loop over a group's pages where a decode program has them
    unrolled, and their padded rows must come out finite too."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as tpu_interpreter)
    from jax.experimental.pallas import tpu as pltpu
    quant = regime.endswith("int8")
    args, want = _edge_case("int8" if quant else "plain", [0, 35, 48, 1],
                            seed=13)
    kw = dict(k_scale=args[5], v_scale=args[6]) if quant else {}
    args = list(args[:5])
    if regime.startswith("grouped"):
        # four query heads to each of the pool's two: rows of one tile (a
        # decode token), and 4 x 256 rows of ONE program a stored head
        args[0] = jnp.asarray(np.random.default_rng(15).standard_normal(
            (_EB, 4 * _ENH, 1, _EHD)).astype(np.float32))
        want = np.array(paged_attention_reference(*args))
        want[np.asarray(args[4]) == 0] = 0.0
        regime = regime[len("grouped-"):]
    heads = args[0].shape[1]
    if regime.startswith("chunk"):
        # 16 rows a lane: an idle lane, a chunk in mid-block over two
        # groups, one ending with the table, one of a single real row
        q0 = np.asarray([0, 24, 32, 0], np.int32)
        args[0] = jnp.asarray(np.random.default_rng(14).standard_normal(
            (_EB, heads, 16, _EHD)).astype(np.float32))
        kw["q_start"] = jnp.asarray(q0)
        want = np.asarray(paged_attention_reference(*args, **kw))
    out = np.asarray(paged_attention(*args, interpret=pltpu.InterpretParams(
        detect_races=True, uninitialized_memory="nan"), **kw))
    assert np.isfinite(out).all()
    if regime.startswith("chunk"):
        for b, n in enumerate(np.asarray(args[4]) - q0):
            np.testing.assert_allclose(out[b, :, :min(n, 16)],
                                       want[b, :, :min(n, 16)], rtol=2e-5,
                                       atol=2e-5)
    else:
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert not tpu_interpreter.races.races_found


# ---------------------------------------------------------------------------
# A prefill chunk (PR 37): T > 1 query rows a lane at positions q_start + row
# against the pool the chunk's own keys are in already. Same small shape as
# the loop's edges (tables of 6 pages of 8 slots, heads of 128), P = 4 pages
# a group; parity with the jnp reference on every REAL row (a row at or past
# ctx is bucket padding: finite, and read by nobody).
# ---------------------------------------------------------------------------

_CHUNKS = {
    # name: (T, q_start a lane, ctx a lane, regime)
    "from_zero": (16, [0], [16], "plain"),
    "block_aligned_start": (16, [16], [32], "plain"),
    "mid_block_start": (16, [13], [29], "plain"),
    "padded_past_ctx": (16, [8], [13], "plain"),
    "ends_in_mid_block": (24, [16], [35], "plain"),
    "one_real_row": (8, [40], [41], "plain"),
    "odd_rows": (10, [3], [13], "plain"),
    "window_drops_leading_pages": (16, [30], [46], "window"),
    "window_inside_the_chunk": (24, [0], [24], "window"),
    "alibi": (16, [13], [27], "alibi"),
    "softcap": (16, [13], [29], "softcap"),
    "stacked_traced_layer": (16, [21], [37], "stacked"),
    "int8": (16, [13], [29], "int8"),
    "lanes_of_their_own": (16, [13, 0, 32, 5], [29, 11, 48, 5], "plain"),
    "lanes_window_stacked": (16, [30, 0, 7, 20], [44, 16, 23, 20],
                             "window"),
    "full_table": (16, [32], [48], "plain"),
}


@pytest.mark.parametrize("case", list(_CHUNKS))
def test_paged_prefill_chunk_matches_reference(case):
    T, q0, ctx, regime = _CHUNKS[case]
    r = _REGIMES[regime]
    B = len(q0)
    q1, kp, vp, bt, _ = _data(B=B, nh=_ENH, hd=_EHD, bs=_EBS,
                              num_blocks=_ENB, nbk=_ENBK, seed=21)
    q = np.random.default_rng(22).standard_normal(
        (B, _ENH, T, _EHD)).astype(np.float32)
    kw = {}
    if "window" in r:
        kw["window"] = jnp.asarray(r["window"], jnp.int32)
    if "slopes" in r:
        kw["alibi_slopes"] = jnp.asarray(r["slopes"])
    if "softcap" in r:
        kw["softcap"] = r["softcap"]
    if r.get("quant"):
        kp, kw["k_scale"], vp, kw["v_scale"], _, _ = _int8_pools(kp, vp)
    if r.get("stacked") or case == "lanes_window_stacked":
        kp, vp = np.stack([kp, kp * 2.0]), np.stack([vp, vp * 0.5])
        kw["layer_idx"] = 1
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(ctx, jnp.int32))
    kw["q_start"] = jnp.asarray(q0, jnp.int32)

    @jax.jit
    def both(*args):
        traced = dict(kw)
        if "layer_idx" in kw:                       # traced, as in the scan
            traced["layer_idx"] = args[-1]
            args = args[:-1]
        return (paged_attention(*args, interpret=True, **traced),
                paged_attention_reference(*args, **traced))

    out, ref = both(*args, *([jnp.asarray(1, jnp.int32)]
                             if "layer_idx" in kw else []))
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.isfinite(out).all()
    assert max(c - s for c, s in zip(ctx, q0)) > 0
    for b in range(B):
        n = min(T, ctx[b] - q0[b])       # 0: a lane with no real row
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("T,nh,want", [
    (1, 32, (32, 4)), (32, 32, (4, 16)), (96, 32, (4, 16)),
    (256, 32, (4, 16)), (256, 16, (4, 16)), (257, 32, (2, 16)),
    (1, 16, (16, 8)), (1024, 16, (1, 16)), (5, 4, (4, 16))],
    ids=lambda v: str(v))
def test_heads_and_pages_shrink_with_the_chunk(T, nh, want):
    """Heads a program and pages a group at the serving cells' widths
    (block 32, heads of 128, bf16, a table of 40): what a decode token gets
    is what it got before the kernel took chunks; a chunk's rows are padded
    to whole tiles of 256 (every chunk shape of a serving loop is ONE
    shape to the kernel), keep a program's accumulator inside its budget
    and take 512 keys a turn (PR 51; 256 until then, by a budget for four
    heads' scores at once), whatever the rows."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _CHUNK_KEYS, _CHUNK_ROWS, _head_group, _pages_per_group,
        _query_rows)
    hg = _head_group(nh, 32, 128, 2, T)
    P = _pages_per_group(hg, 32, 128, 2, 40, False, T)
    assert (hg, P) == want
    assert (hg, P) == (_head_group(nh, 32, 128, 2),
                       _pages_per_group(hg, 32, 128, 2, 40)) or T > 1
    if T > 1:
        assert hg * _query_rows(T) <= _CHUNK_ROWS or hg == 1
        assert P * 32 == _CHUNK_KEYS


@pytest.mark.parametrize("shape,want", [
    # (heads a program, block, head_dim, item size, table, int8 tier)
    ((16, 32, 128, 2, 128, False), 8),     # serve-olmoe-1b-7b-l8-gen
    ((32, 32, 128, 2, 40, False), 4),      # serve-mistral-7b-l16-chat
    ((16, 32, 128, 1, 128, True), 8),      # the same two on the int8 tier
    ((32, 32, 128, 1, 40, True), 4),
    ((16, 32, 128, 2, 32, False), 8),      # gpt2-1.3b, chip_smoke.py
    ((16, 32, 128, 2, 3, False), 2),       # never more than the table holds
    ((16, 32, 128, 2, 1, False), 1),
    ((64, 128, 256, 4, 64, False), 1),     # a page over the budget: one
], ids=["olmoe", "mistral", "olmoe-int8", "mistral-int8", "gpt2",
        "table-of-3", "table-of-1", "huge-page"])
def test_pages_per_group_from_shapes(shape, want):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _VMEM_BUDGET, _pages_per_group, _scale_lanes)
    hg, bs, hd, itemsize, nbk, quant = shape
    P = _pages_per_group(*shape)
    assert P == want
    assert 1 <= P <= nbk
    held = 2 * 2 * hg * P * bs * hd * itemsize
    if quant:
        held += 2 * 2 * hg * P * 8 * _scale_lanes(bs) * 4
    assert held <= _VMEM_BUDGET or P == 1, (held, _VMEM_BUDGET)


# ---------------------------------------------------------------------------
# A chunk's turn at the serving cells' geometry (PR 51): pages of 32 slots,
# heads of 128, so a program's copy group is 16 pages = 512 keys, taken as
# four lane tiles of 128, a query head at a time, the running sum a lane
# apart.
# The edges of that geometry against the jnp reference, at the tolerance the
# small shapes above are held to.
# ---------------------------------------------------------------------------

_LBS, _LHD, _LNBK, _LNB, _LWIN = 32, 128, 40, 130, 200
_LANE_CASES = {
    # name: (query heads, stored heads, T, q_start a lane, ctx a lane, regime)
    "ends_inside_a_lane_tile": (2, 2, 64, [136], [200], "plain"),
    "ends_on_a_tile_edge": (2, 2, 64, [192], [256], "plain"),
    "ends_on_a_group_edge": (2, 2, 64, [448], [512], "plain"),
    "one_key_past_a_group": (2, 2, 64, [449], [513], "plain"),
    # the window of the first row starts at key 501, on page 15 of group 0
    "window_first_page_inside_a_group": (2, 2, 64, [700], [764], "window"),
    "window_inside_the_first_group": (2, 2, 64, [250], [314], "window"),
    "cached_context_beside_a_first_chunk": (2, 2, 64, [600, 0], [664, 64],
                                            "plain"),
    "idle_lane_between": (2, 2, 32, [515, 0, 40], [547, 0, 72], "plain"),
    "padded_rows_past_ctx": (2, 2, 96, [500], [530], "plain"),
    "two_row_tiles": (2, 1, 300, [300], [600], "plain"),
    "full_table": (2, 2, 64, [1216], [1280], "plain"),
    "k_exaone_split_programs": (16, 2, 64, [530], [594], "window"),
    "olmoe_four_stored_heads": (4, 4, 64, [530], [594], "plain"),
    "mistral_group_of_four": (8, 2, 64, [449], [513], "plain"),
    "int8": (2, 2, 64, [449], [513], "int8"),
    "int8_four_stored_heads": (4, 4, 64, [130], [194], "int8"),
    "alibi": (2, 2, 64, [449], [513], "alibi"),
    "softcap": (2, 2, 64, [449], [513], "softcap"),
    # a learned indexer's selection (top ``_LTOPK`` of a row's keys, equal
    # scores to the lower position): rows of at most and of more than
    # ``_LTOPK`` keys in one call; behind a cached context; beside a window;
    # a lane behind a context beside a lane's first chunk
    "select_rows_below_and_above_topk": (8, 2, 96, [60], [156], "select"),
    "select_behind_a_context": (2, 2, 64, [449], [513], "select"),
    "select_under_a_window": (2, 2, 64, [700], [764], "select-window"),
    "select_two_lanes": (8, 2, 64, [600, 0], [664, 64], "select"),
    "select_split_programs": (16, 2, 64, [530], [594], "select"),
}
_LTOPK = 100


def _lane_case(case, seed=41):
    """``(args, kw, plan)`` of one call at the cells' geometry."""
    from deepspeed_tpu.ops.pallas.paged_attention import _plan
    nh, kvh, T, q0, ctx, regime = _LANE_CASES[case]
    B = len(q0)
    _, kp, vp, bt, _ = _data(B=B, nh=kvh, hd=_LHD, bs=_LBS, num_blocks=_LNB,
                             nbk=_LNBK, seed=seed)
    q = np.random.default_rng(seed + 1).standard_normal(
        (B, nh, T, _LHD)).astype(np.float32)
    kw = {"q_start": jnp.asarray(q0, jnp.int32)}
    if regime.endswith("window"):
        kw["window"] = jnp.asarray(_LWIN, jnp.int32)
    if regime.startswith("select"):
        from deepspeed_tpu.ops.pallas import sparse_select as ss
        rng = np.random.default_rng(seed + 2)
        pos = np.arange(ss.padded_keys(_LNBK * _LBS))[None, None]
        seen = (pos <= (np.asarray(q0)[:, None] + np.arange(T))[:, :, None]
                ) & (pos < np.asarray(ctx)[:, None, None])
        sc = np.round(rng.standard_normal(seen.shape) * 2) / 2    # ties
        kw["select"] = ss.select(jnp.asarray(
            np.where(seen, sc, -np.inf), jnp.float32), _LTOPK, kernel=False)
    if regime == "alibi":
        kw["alibi_slopes"] = jnp.asarray(
            [2.0 ** -(1 + 8 * i / nh) for i in range(nh)], jnp.float32)
    if regime == "softcap":
        kw["softcap"] = 30.0
    if regime == "int8":
        kp, kw["k_scale"], vp, kw["v_scale"], _, _ = _int8_pools(kp, vp)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(ctx, jnp.int32))
    plan = _plan(nh, kvh, _LBS, _LHD, kp.dtype.itemsize, _LNBK, T,
                 regime == "int8", regime.startswith("select"))
    return args, kw, plan


@pytest.mark.parametrize("case", list(_LANE_CASES))
def test_chunk_turn_of_lane_tiles_matches_reference(case):
    nh, kvh, T, q0, ctx, _ = _LANE_CASES[case]
    args, kw, (hg, gq, rows, P, lanes) = _lane_case(case)
    assert (P * _LBS, lanes) == (512, 128)      # a turn: four lane tiles
    assert rows == -(-T // 256) * 256 and hg * gq * rows <= 1024
    static = {n: kw.pop(n) for n in ("softcap",) if n in kw}
    out, ref = jax.jit(lambda args, kw: (
        paged_attention(*args, interpret=True, **kw, **static),
        paged_attention_reference(*args, **kw, **static)))(args, kw)
    out, ref = np.asarray(out), np.asarray(ref)
    assert out.shape == (len(q0), nh, T, _LHD) and np.isfinite(out).all()
    for b, (s, c) in enumerate(zip(q0, ctx)):
        n = min(T, c - s)
        np.testing.assert_allclose(out[b, :, :n], ref[b, :, :n], rtol=2e-5,
                                   atol=2e-5)
    if "select" in kw:
        # the selection selected: rows that see more than ``_LTOPK`` keys
        # differ from dense attention, the others do not
        dense = np.asarray(paged_attention_reference(
            *args, **{k: v for k, v in kw.items() if k != "select"}))
        for b, (s, c) in enumerate(zip(q0, ctx)):
            few = max(0, min(T, c - s, _LTOPK - s))
            if "window" not in kw:
                np.testing.assert_allclose(out[b, :, :few], dense[b, :, :few],
                                           rtol=2e-5, atol=2e-5)
            n = min(T, c - s)
            assert few == n or np.abs(out[b, :, few:n]
                                      - dense[b, :, few:n]).max() > 1e-3


@pytest.mark.parametrize("case", ["ends_inside_a_lane_tile",
                                  "one_key_past_a_group",
                                  "window_first_page_inside_a_group",
                                  "window_inside_the_first_group",
                                  "two_row_tiles", "full_table"])
def test_chunk_walk_is_the_kernels_own_rule(case):
    """``chunk_walk`` (what ``serving.engine`` counts by) against the kernel
    itself: the pages outside the turns it names are never read (their
    table entries point at a block of NaN), and a key in a lane tile it
    does not call live reaches no row (filled with large values, the output
    is bit for bit what it was), while its live tiles hold every key some
    row sees."""
    from deepspeed_tpu.ops.pallas.paged_attention import chunk_plan, chunk_walk
    nh, kvh, T, (q0,), (ctx,), regime = _LANE_CASES[case]
    args, kw, _ = _lane_case(case)
    window = _LWIN if regime == "window" else 0
    _, P, lanes = chunk_plan(nh, kvh, _LBS, _LHD, 4, _LNBK, T)
    assert (P * _LBS, lanes) == (512, 128)
    turns, tiles, live = chunk_walk(q0, ctx, window, T, P, lanes, _LBS,
                                    _LNBK)
    # by hand: the groups from the first row's window to the last real key,
    # and of their tiles a row tile those that hold a key one of its real
    # rows sees
    low = max(q0 + 1 - window, 0) if window else 0
    g0, g1 = low // _LBS // P, -(-(-(-ctx // _LBS)) // P)
    assert turns == g1 - g0 and tiles == turns * 4 * -(-T // 256)
    seen = np.zeros((-(-T // 256), g1 * 4), bool)
    for r in range(min(T, ctx - q0)):
        lo = max(q0 + r + 1 - window, 0) if window else 0
        seen[r // 256, lo // 128:(q0 + r) // 128 + 1] = True
    assert live == seen[:, g0 * 4:].sum() and not seen[:, :g0 * 4].any()
    q, kp, vp, bt, lens = args
    call = jax.jit(lambda kp, vp, bt: paged_attention(
        q, kp, vp, bt, lens, interpret=True, **kw))
    want = np.asarray(call(kp, vp, bt))
    # a page outside the named turns: NaN, and nobody reads it
    bt_np = np.asarray(bt).copy()
    spare = next(i for i in range(1, _LNB) if i not in bt_np)
    poisoned = np.ones(_LNBK, bool)
    poisoned[g0 * P:min(g1 * P, _LNBK)] = False
    bt_np[0, poisoned] = spare
    nan = lambda pool: pool.at[:, spare].set(jnp.nan)
    got = np.asarray(call(nan(kp), nan(vp), jnp.asarray(bt_np)))
    assert np.array_equal(got[0, :, :ctx - q0], want[0, :, :ctx - q0])
    # a tile no row tile sees: large keys and values change nothing
    dead = ~seen.any(0)
    kp2, vp2 = np.array(kp), np.array(vp)
    for t in np.flatnonzero(dead):
        for page in range(t * 4, min(t * 4 + 4, _LNBK)):
            kp2[:, bt_np[0, page]] = vp2[:, bt_np[0, page]] = 1e3
    if dead[g0 * 4:].any():
        got = np.asarray(call(jnp.asarray(kp2), jnp.asarray(vp2), bt))
        assert np.array_equal(got[0, :, :ctx - q0], want[0, :, :ctx - q0])
    # ... and in a live tile they do
    kp2[:, bt_np[0, (ctx - 1) // _LBS]] = 1e3
    got = np.asarray(call(jnp.asarray(kp2), vp, bt))
    assert not np.array_equal(got[0, :, :ctx - q0], want[0, :, :ctx - q0])


# ---------------------------------------------------------------------------
# Grouped-query models (PR 44): the pool stores the model's KV heads, and the
# ``group = nh // kvh`` query heads that share a stored head are ROWS of that
# head's query tile: a decode token of each on a row of its own (a group of
# 16 takes two sublane tiles), a chunk's rows head after head, a row's
# position ``q_start + row % tile``; where ``group x tile`` is more than a
# program holds (8 and 16 heads of 256 rows) the group's heads are split over
# programs that copy the same stored head. The kernel against the gather
# reference, and the reference against itself over the pool expanded to a
# row a query head (``group`` 1: what the pool held before).
# ---------------------------------------------------------------------------

_GKVH = 2
_GSHAPES = {
    # name: (T, q_start a lane, ctx a lane)
    "decode": (1, [28, 0, 47, 8], [29, 0, 48, 9]),
    "chunk_aligned": (16, [16, 0], [32, 16]),
    "chunk_mid_block": (16, [13, 5], [29, 21]),
    "chunk_padded_rows": (16, [8, 40], [13, 41]),
}


def _grouped_case(group, shape, regime, seed=31):
    """``(args, kw)`` of one grouped call: ``_GKVH`` stored heads, ``group``
    query heads each, on the loop's edge shape (pages of 8 slots, heads of
    128, a table of 6)."""
    T, q0, ctx = _GSHAPES[shape]
    r = _REGIMES[regime]
    B, nh = len(q0), _GKVH * group
    _, kp, vp, bt, _ = _data(B=B, nh=_GKVH, hd=_EHD, bs=_EBS, num_blocks=_ENB,
                             nbk=_ENBK, seed=seed)
    q = np.random.default_rng(seed + 1).standard_normal(
        (B, nh, T, _EHD)).astype(np.float32)
    kw = {}
    if "window" in r:
        kw["window"] = jnp.asarray(r["window"], jnp.int32)
    if "slopes" in r:
        kw["alibi_slopes"] = jnp.asarray(
            [2.0 ** -(1 + 8 * i / nh) for i in range(nh)], jnp.float32)
    if "softcap" in r:
        kw["softcap"] = r["softcap"]
    if r.get("quant"):
        kp, kw["k_scale"], vp, kw["v_scale"], _, _ = _int8_pools(kp, vp)
    if r.get("stacked"):
        kp, vp = np.stack([kp, kp * 2.0]), np.stack([vp, vp * 0.5])
        kw["layer_idx"] = jnp.asarray(1, jnp.int32)
    if T > 1:
        kw["q_start"] = jnp.asarray(q0, jnp.int32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(ctx, jnp.int32))
    return args, kw


def _expanded(args, kw, group):
    """The same call over a pool that holds a row a QUERY head."""
    rep = lambda a: jnp.repeat(a, group, axis=a.ndim - 4)
    wide = dict(kw)
    if "k_scale" in kw:             # [(L,) kvh, blocks, slots, 1]
        wide["k_scale"], wide["v_scale"] = rep(kw["k_scale"]), rep(
            kw["v_scale"])
    return (args[0], rep(args[1]), rep(args[2])) + args[3:], wide


def _real_rows(out, ref, shape):
    """Every real row of every lane (an idle decode lane gives zeros; a
    chunk's rows at or past ctx are padding nobody reads)."""
    T, q0, ctx = _GSHAPES[shape]
    for b, (s, c) in enumerate(zip(q0, ctx)):
        n = min(T, c - s) if T > 1 else int(c > 0)
        yield out[b, :, :n], ref[b, :, :n]


#: every group against every shape with nothing else on; each other regime
#: on a decode tile filled, a chunk of one program a stored head and a chunk
#: split over programs (the whole product is 120 interpreted kernels, three
#: times this file's other tests together, and finds no more)
_GCASES = [(g, s, "plain") for g in (1, 2, 4, 8, 16) for s in _GSHAPES] + [
    (g, s, r) for r in _REGIMES if r != "plain"
    for g, s in ((4, "chunk_mid_block"), (8, "decode"),
                 (16, "chunk_padded_rows"))]


@pytest.mark.parametrize("group,shape,regime", _GCASES,
                         ids=["-".join(map(str, c)) for c in _GCASES])
def test_grouped_query_heads_share_a_stored_head(group, shape, regime):
    args, kw = _grouped_case(group, shape, regime)
    static = {n: kw.pop(n) for n in ("softcap",) if n in kw}    # a float

    @jax.jit
    def both(args, kw):
        kw = {**kw, **static}
        wide_args, wide_kw = _expanded(args, kw, group)
        return (paged_attention(*args, interpret=True, **kw),
                paged_attention_reference(*args, **kw),
                paged_attention_reference(*wide_args, **wide_kw))

    out, ref, wide = (np.asarray(a) for a in both(args, kw))
    assert out.shape == args[0].shape and np.isfinite(out).all()
    for got, want in _real_rows(out, ref, shape):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for got, want in _real_rows(ref, wide, shape):
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("nh,kvh,T,want", [
    # (query heads, stored heads, rows a head) -> (stored heads a program,
    # query heads of a stored head a program, programs a lane, rows, pages)
    (32, 8, 1, (8, 4, 1, 8, 4)),            # mistral: a decode token
    (32, 8, 256, (1, 4, 8, 1024, 16)),      # ... a chunk: one tile a group
    (64, 8, 1, (8, 8, 1, 8, 2)),            # K-EXAONE: the tile filled
    (64, 8, 256, (1, 4, 16, 1024, 16)),     # ... 8 x 256 rows: two programs
    (64, 8, 512, (1, 2, 32, 1024, 16)),
    (32, 4, 1, (4, 8, 1, 8, 4)),            # llama-1.1b's heads at 128 wide
    (32, 2, 1, (2, 16, 1, 16, 4)),          # a group of 16: two sublane tiles
    (16, 16, 1, (16, 1, 1, 8, 8)),          # OLMoE, gpt2: nothing to group
    (16, 16, 256, (4, 1, 4, 256, 16)),
    (32, 32, 256, (4, 1, 8, 256, 16)),      # the pool the parent stored
], ids=lambda v: str(v))
def test_group_tile_from_shapes(nh, kvh, T, want):
    """The tile of one stored head at the serving cells' widths (block 32,
    heads of 128, bf16, a table of 128): what a model with nothing to group
    gets is what it got, a chunk's programs keep their rows within
    ``_CHUNK_ROWS`` by splitting a group's query heads over programs, and
    a decode token's pages a group are what the QUERY heads got when the
    pool stored a row for each (more of them cost set-up and no time:
    PERF.md, PR 44); a chunk's are 512 keys (PR 51)."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _CHUNK_ROWS, _pages_per_group, _program_heads, _query_rows)
    group = nh // kvh
    hg, gq = _program_heads(nh, kvh, 32, 128, 2, T)
    P = _pages_per_group(hg * gq, 32, 128, 2, 128, False, T)
    # a decode tile: a row a query head of the group; a chunk's: a tile of
    # 256-row multiples a query head, hg x gq of them a program
    rows = _query_rows(T, group) if T == 1 else gq * _query_rows(T)
    assert (hg, gq, kvh // hg * (group // gq), rows, P) == want
    assert hg * rows <= _CHUNK_ROWS
    if group > 1 and T > 1:
        assert group * _query_rows(T) > _CHUNK_ROWS or gq == group


def test_query_heads_must_share_the_stored_heads_evenly():
    args, kw = _grouped_case(3, "decode", "plain")
    bad = (args[0][:, :5],) + args[1:]
    for fn in (lambda *a: paged_attention(*a, interpret=True),
               paged_attention_reference):
        with pytest.raises(ValueError, match="stored heads"):
            fn(*bad)


# tier-2 (round-17 budget sweep, ~9s): the cheaper tier-1 cousins are
# test_paged_int8_parity_all_regimes (kernel+reference vs dequant oracle)
# and test_serving.test_int8_kv_pool_parity_jnp_and_kernel (engine-level
# token parity); scripts/tier2.sh runs this full-plumbing GQA+rotary leg
@pytest.mark.slow
def test_paged_int8_gqa_rotary_decode_kernel_vs_reference():
    """GQA + rotary through the full decode plumbing: a llama-ish
    paged_forward prefill writes the int8 pool (kv heads repeated to full
    heads upstream, rotary applied before the write), then ONE decode step
    runs twice — interpret=True (Pallas int8 kernel, in-VMEM dequant) and
    interpret=False (jnp reference, post-gather dequant). Same pool bytes,
    same logits, same greedy token."""
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.serving.kv_cache import init_pool
    from deepspeed_tpu.serving.model_runner import paged_forward
    model, cfg = build_model(
        "llama-1.1b", hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, mlp_dim_override=64, vocab_size=64, max_seq_len=64,
        dtype=jnp.float32)
    # attention_impl stays "auto": a "reference" model serves on the gather
    # oracle whatever ``interpret`` says (serving/model_runner.py), and the
    # kernel leg must really hold the kernel
    assert cfg.attention_impl == "auto"
    ids = np.asarray([[3, 1, 4, 1, 5, 9, 2], [6, 5, 3, 5, 8, 9, 7]],
                     np.int32)
    params = ensure_scan_layout(
        model.init(jax.random.PRNGKey(1), {"input_ids": ids})["params"],
        cfg.num_layers)
    bs, nbk = 16, 2
    bt = np.asarray([[1, 2], [3, 4]], np.int32)
    T = ids.shape[1]
    run = lambda interp: _gqa_decode(cfg, params, ids, bt, bs, nbk, interp)
    logits_k, n_kernels = run(True)
    logits_r, n_ref_kernels = run(False)
    # one paged_attention pallas_call in the scanned layer body of the
    # kernel leg, none in the reference leg — a routing change cannot
    # quietly turn this into reference-vs-reference
    assert n_kernels >= 1 and n_ref_kernels == 0, (n_kernels, n_ref_kernels)
    np.testing.assert_allclose(logits_k, logits_r, rtol=2e-5, atol=2e-5)
    assert np.array_equal(logits_k[:, -1].argmax(-1),
                          logits_r[:, -1].argmax(-1))


def _gqa_decode(cfg, params, ids, bt, bs, nbk, interpret):
    from deepspeed_tpu.serving.kv_cache import init_pool
    from deepspeed_tpu.serving.model_runner import paged_forward
    B, T = ids.shape
    pools = init_pool(cfg, 8, bs, dtype=jnp.int8)
    zeros = jnp.zeros((B,), jnp.int32)
    # prefill (T > 1: the same routing as the decode step) populates the
    # int8 pool
    _, pools = paged_forward(cfg, params, jnp.asarray(ids), pools,
                             jnp.asarray(bt), zeros,
                             jnp.full((B,), T, jnp.int32), bs,
                             interpret=interpret)
    nxt = jnp.asarray([[7], [2]], jnp.int32)
    decode = lambda pools: paged_forward(
        cfg, params, nxt, pools, jnp.asarray(bt),
        jnp.full((B,), T, jnp.int32), jnp.full((B,), T + 1, jnp.int32), bs,
        interpret=interpret)[0]
    n_kernels = str(jax.make_jaxpr(decode)(pools)).count("pallas_call")
    return np.asarray(decode(pools)), n_kernels
