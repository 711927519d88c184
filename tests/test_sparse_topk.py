"""``sparse_topk`` (``ops/pallas/sparse_select.py::topk_threshold``), the
kernel interpreted on the CPU against ``topk_threshold_reference``, always by
the SELECTED SETS: ``thr`` itself is any float that separates a row's k keys,
so only :func:`selected` may read it. The kernel works in proportion to what
a tile of rows can see (the column extent it is handed) and stops when a
pass can no longer change the answer; none of that may change which keys."""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import sparse_select as ss

K, KP = 64, 2048            # a column step of 1024: two of them
STEP = ss.topk_columns(np.asarray([1]), KP)[0]
ROWS = ss._TOPK_ROWS        # rows a tile


def tile(extents):
    """One tile's rows from a pattern of extents (repeated to its rows)."""
    return np.resize(np.asarray(extents), ROWS)


def sets(scores, thr, tie):
    scores = jnp.asarray(scores, jnp.float32)
    return np.asarray(ss.selected(
        scores, thr[:, None], tie[:, None], jnp.arange(scores.shape[1]))
        & (scores > -jnp.inf))


def visible(scores, extent, window=None):
    """``scores`` with ``-inf`` where a row does not see a key: at or past
    its ``extent``, or more than ``window`` keys back."""
    col, extent = np.arange(scores.shape[1])[None], np.asarray(extent)[:, None]
    see = col < extent
    if window is not None:
        see &= col >= extent - window
    return np.where(see, scores, -np.inf).astype(np.float32)


def kernel_and_oracle(scores, seen=None, extent=None, *, clean=None, k=K):
    """The kernel's sets on ``scores``, the oracle's on ``clean`` (``scores``
    unless the kernel was handed something it must not read), the passes."""
    thr, tie, passes = ss.topk_threshold(
        jnp.asarray(scores), k, seen, extent, return_passes=True,
        interpret=True)
    clean = scores if clean is None else clean
    want = sets(clean, *ss.topk_threshold_reference(jnp.asarray(clean), k))
    return sets(clean, thr, tie), want, np.asarray(passes)


def drawn(rows, seed=0, keys=KP):
    return np.random.default_rng(seed).standard_normal(
        (rows, keys)).astype(np.float32)


EXTENTS = {
    # every row sees at most k keys: the tile makes no pass
    "under_k": [5, 17, 33, 40, 52, 60, 63, 64],
    # one row over k among seven under: those seven keep every key
    "one_over_k": [900, 5, 17, 33, 52, 60, 63, 64],
    # a few columns past a column step's edge
    "past_a_step": [STEP - 5, STEP - 4, STEP - 3, STEP - 2, STEP - 1, STEP,
                    STEP + 1, STEP + 3],
    "full": [KP],
}


@pytest.mark.parametrize("handed", [False, True], ids=["counted", "handed"])
@pytest.mark.parametrize("case", EXTENTS)
def test_the_extent_bounds_the_work_and_not_the_answer(case, handed):
    extent = tile(EXTENTS[case])
    if case == "one_over_k":
        extent[1:] = np.minimum(extent[1:], K)      # ONE row over k
    scores = visible(drawn(ROWS), extent)
    got, want, passes = kernel_and_oracle(
        scores, *((extent, extent) if handed else ()))
    assert np.array_equal(got, want)
    assert (want.sum(axis=1) == np.minimum(extent, K)).all()
    assert (passes[0, 0] == 0) == (case == "under_k")
    handed_out = ss.topk_tiles(extent, extent, K, KP, np)
    assert handed_out.tolist() == [0 if case == "under_k" else extent.max()]
    assert ss.topk_columns(handed_out, KP).tolist() == [
        {"under_k": 0, "one_over_k": STEP}.get(case, KP)]


def test_what_lies_past_the_extent_is_not_read():
    """Finite garbage beyond the tile's extent, larger than every score:
    with the extent handed in, the selection is that of the clean rows."""
    extent = tile([300, 310, 320, 330, 340, 350, 360, STEP + 70])
    clean = visible(drawn(ROWS, 1), extent)
    dirty = np.where(np.arange(KP)[None] < extent.max(), clean,
                     np.float32(1e30))
    got, want, _ = kernel_and_oracle(dirty, extent, extent, clean=clean)
    assert np.array_equal(got, want)
    # not handed in, the garbage is scores like any other
    got, want, _ = kernel_and_oracle(dirty)
    assert np.array_equal(got, want) and got[:, extent.max():].any()


@pytest.mark.parametrize("window", [48, 200])
def test_a_window_clips_the_count_and_not_the_positions(window):
    """A windowed layer's rows see ``window`` keys ending where they stand:
    under k of them (48) the tile makes no pass though its rows stand far
    beyond k; over k (200) it bisects up to the last row's POSITION."""
    extent = 900 + np.arange(ROWS)
    scores = visible(drawn(ROWS, 2), extent, window)
    seen = np.minimum(extent, window)
    got, want, passes = kernel_and_oracle(scores, seen, extent)
    assert np.array_equal(got, want)
    assert (want.sum(axis=1) == min(window, K)).all()
    assert (passes[0, 0] > 0) == (window > K)
    assert ss.topk_tiles(seen, extent, K, KP, np).tolist() == [
        extent.max() if window > K else 0]


def _coarse(rows, seed):
    s = np.round(drawn(rows, seed) * 2) / 2
    s[s == 0] = -0.0
    return s


TIES = {
    # a coarse grid: many scores equal to the k-th, on both sides of it
    "coarse_grid": lambda: _coarse(ROWS, 3),
    "all_equal": lambda: np.full((ROWS, KP), 1.5, np.float32),
    # exact zeros, -0.0 among them, straddling the threshold
    "zeros": lambda: np.where(
        np.arange(KP)[None] % 3 == 0, np.float32(-0.0), np.where(
            np.arange(KP)[None] % 3 == 1, np.float32(0.0),
            -np.abs(drawn(ROWS, 4)))).astype(np.float32),
}


@pytest.mark.parametrize("case", TIES)
def test_equal_scores_go_to_the_lower_position(case):
    extent = tile([700, 800, 900, 1000, 1100, 1200, 1300, 1400])
    scores = visible(TIES[case](), extent)
    got, want, passes = kernel_and_oracle(scores, extent, extent)
    assert np.array_equal(got, want)
    assert (want.sum(axis=1) == K).all()
    # a partial tie runs out of value bits and is broken by position, over
    # the bits of the tile's extent
    assert passes[0].tolist() == [32, 11]


SHAPES = {
    # a decode call's tile: lanes of different contexts, idle ones among
    # them
    "decode": tile([70, 1, 900, 300, 1, 2000, 1, 130]),
    # a chunk's: consecutive positions
    "chunk": 1500 + np.arange(ROWS),
    "chunk_crossing_k": K - 4 + np.arange(ROWS),
}


@pytest.mark.parametrize("shape", SHAPES)
def test_the_serving_shapes(shape):
    extent = SHAPES[shape]
    scores = visible(drawn(ROWS, 5), extent)
    got, want, passes = kernel_and_oracle(scores, extent, extent)
    assert np.array_equal(got, want)
    assert 0 < passes[0, 0] < 32 and passes[0, 1] == 0


def test_more_rows_than_a_tile_and_a_ragged_last_tile():
    """Three tiles, the first idle, the last of three rows and padded; each
    with its own extent and its own passes."""
    extent = np.concatenate([np.full(ROWS, 3), 400 + np.arange(ROWS),
                             [1900, 1950, 2000]])
    scores = visible(drawn(2 * ROWS + 3, 6), extent)
    got, want, passes = kernel_and_oracle(scores, extent, extent)
    assert np.array_equal(got, want)
    assert passes.shape == (3, 2) and passes[0].tolist() == [0, 0]
    assert (passes[1:, 0] > 0).all() and (passes[:, 1] == 0).all()
    assert ss.topk_tiles(extent, extent, K, KP, np).tolist() == [
        0, 399 + ROWS, 2000]


@pytest.mark.parametrize("keys", [3000, 8000])
def test_rows_of_index_scores_separate_long_before_bit_zero(keys):
    """Scores as the indexer's formula draws them (``sum_j w_j relu(qI_j .
    kI)``, 16 heads of 64, normal operands), k 2048 as published: a tile
    stops well short of the 32 value passes and makes no position pass (the
    reckoning ISSUE 46 sized the kernel's work by)."""
    rng = np.random.default_rng(keys)
    q = rng.standard_normal((ROWS, 16, 64)).astype(np.float32)
    key = rng.standard_normal((keys, 64)).astype(np.float32)
    w = rng.standard_normal((ROWS, 16)).astype(np.float32)
    s = np.einsum("th,thk->tk", w, np.maximum(
        np.einsum("thd,kd->thk", q, key), 0))
    Kp = ss.padded_keys(keys)
    extent = keys - np.arange(ROWS)[::-1]
    scores = visible(np.pad(s, [(0, 0), (0, Kp - keys)]), extent)
    got, want, passes = kernel_and_oracle(scores, extent, extent, k=2048)
    assert np.array_equal(got, want)
    assert 12 <= passes[0, 0] <= 28 and passes[0, 1] == 0


def test_thr_is_a_separator_and_readers_go_through_selected():
    """A separated row's ``thr`` lies in the gap under its k-th score (with
    ``tie`` = every position), so it need not be a score of the row."""
    scores = visible(drawn(ROWS, 7), [KP] * ROWS)
    thr, tie = ss.topk_threshold(jnp.asarray(scores), K, interpret=True)
    ranked = np.sort(scores, axis=1)[:, ::-1]
    assert (np.asarray(thr) <= ranked[:, K - 1]).all()
    assert (np.asarray(thr) > ranked[:, K]).all()
    assert (np.asarray(tie) == KP).all()
