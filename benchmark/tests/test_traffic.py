"""The traffic generator is a pure function of the mix file and the seed."""
import itertools

import numpy as np

from benchmark import harness, traffic

CHAT = harness.load_cell("serve-mistral-7b-l16-chat").traffic
PRETRAIN = harness.load_cell("train-gpt2-1.3b-z3").traffic


def take(it, n):
    return list(itertools.islice(it, n))


def test_request_stream_repeats_for_one_seed_and_differs_for_another():
    a = take(traffic.request_stream(CHAT, 32000, 2 ** 31 + 5), 70)
    b = take(traffic.request_stream(CHAT, 32000, 2 ** 31 + 5), 70)
    c = take(traffic.request_stream(CHAT, 32000, 7), 70)
    assert a == b
    assert [p for p, _ in a] != [p for p, _ in c]          # other tokens
    # ... but the same work: every seed offers the same sizes in one order
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in c]


def test_request_sizes_follow_the_mix_file():
    sizes = take(traffic.request_sizes(CHAT), 3 * CHAT["cycle"])
    prompts = [p for p, _ in sizes]
    outs = [o for _, o in sizes]
    assert min(prompts) >= 32 and max(prompts) <= 1024
    assert min(outs) >= 16 and max(outs) <= 256
    # clipped lognormals of median 192 / 64: the issue's means (~264, ~77)
    assert 240 < np.mean(prompts) < 290 and 70 < np.mean(outs) < 85
    assert abs(np.median(prompts) - 192) < 12 and abs(np.median(outs) - 64) < 4
    # every cycle holds the same multiset, in another order
    n = CHAT["cycle"]
    assert sorted(sizes[:n]) != sizes[:n]
    assert sorted(prompts[:n]) == sorted(prompts[n:2 * n])
    assert prompts[:n] != prompts[n:2 * n]


def test_token_batches_shape_and_seed():
    a = take(traffic.token_batches(PRETRAIN, 50257, 2 ** 31 + 5, 1), 2)
    b = take(traffic.token_batches(PRETRAIN, 50257, 2 ** 31 + 5, 1), 2)
    four = next(traffic.token_batches(PRETRAIN, 50257, 1, 4))
    assert a[0].shape == (8, 1024) and a[0].dtype == np.int32
    assert four.shape == (32, 1024)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == a[1]).all()                  # a fresh batch a step
    assert 0 <= a[0].min() and a[0].max() < 50257


def test_mixsim_replays_a_mix_the_same_way_twice():
    """The replay is a pure function of the mix, the serving settings and the
    two step costs, and a time scales with the step costs."""
    from benchmark import mixsim
    mix = dict(CHAT, clients=4, cycle=8,
               prompt_len=dict(CHAT["prompt_len"], median=48, max=128),
               output_len=dict(CHAT["output_len"], median=8, max=16, min=4))
    serving = {"block_size": 16, "pool_blocks": 48, "max_batch": 4,
               "max_blocks_per_seq": 10, "prefill_chunk_tokens": 32,
               "prefix_cache": True}
    kw = dict(seconds=3.0, decode_s=0.010, prefill_s=0.015)
    a = mixsim.replay(mix, serving, **kw)
    assert a == mixsim.replay(mix, serving, **kw)
    assert a["requests"] > 20 and abs(a["sim_itl_p95_ms"] - 15.0) < 1e-6
    half = mixsim.replay(mix, serving, scale=0.5, **kw)
    assert half["requests"] > a["requests"]
    assert abs(half["sim_itl_p95_ms"] - a["sim_itl_p95_ms"]) < 1e-6
