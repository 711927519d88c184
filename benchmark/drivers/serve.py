"""Serving cells: ``init_inference(model, cfg, model_parameters=params)
.serve()`` driven by a closed loop of clients from this one thread (the loop
of ``chip_smoke._serve``, without its second thread: a client that waits for
its reply needs none).

Set-up: weights made on the device from the seed in one jitted call, in the
type they are served in; the engine; warm-up of the decode program and of
every prefill shape the mix can produce, together with the requests that
``correct`` is decided on (served beside each other on the loop's own
programs); then the ramp: the closed loop runs until as many requests as
there are clients have had their first token. The window starts there. The
plain reference reads the checked requests once the window has closed, the
peak of the device's memory has been read and the engine is dropped: its time
is no part of ``setup_s``.

Every token is stamped by this file after the ``srv.step()`` that produced
it (the step ends in the fetch of the tokens; the program keeps no per-token
stamp). The window ends with the step that crosses ``--seconds``; requests
submitted inside it that have no first token yet are then stepped to their
first token, so that the tail is the tail of all of them; tokens after the
mark count for nothing.
"""

from __future__ import annotations

import gc
import inspect
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import harness, reduce, reference, trace as tracing
from benchmark.traffic import request_stream, seeded_tokens

#: a request submitted inside the window must reach its first token within
#: this long after the window's end, else it counts as failed
DRAIN_LIMIT_S = 60.0


def make_params(model, mcfg, seed: int, dtype):
    """The whole parameter tree on the device from one jitted call: kernels
    N(0, 1/fan_in), embeddings N(0, 1/hidden), norm scales 1, biases 0 (the
    spread of the model's own initialisers), drawn in ``dtype``."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           {"input_ids": jnp.zeros((1, 128), jnp.int32)})
    )["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = getattr(path[-1], "key", str(path[-1]))
            if name == "scale":
                out.append(jnp.ones(leaf.shape, dtype))
            elif name == "bias":
                out.append(jnp.zeros(leaf.shape, dtype))
            else:
                fan_in = leaf.shape[-2] if name == "kernel" \
                    else leaf.shape[-1]
                out.append((jax.random.normal(jax.random.fold_in(key, i),
                                              leaf.shape, jnp.float32)
                            * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(harness.jax_seed(seed)))


def cell_model(cell: harness.Cell, family):
    """``(model, its config, the parameter type)`` of the cell's
    configuration, as it is served."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, build_model
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[cell.system["dtype"]]
    model, mcfg = build_model(TransformerConfig(
        **family.model_kwargs(cell.config), dtype=dtype))
    return model, mcfg, dtype


def start_engine(cell: harness.Cell, model, params, rehearsal: bool):
    """``init_inference(model, <the cell's inference config>.json,
    model_parameters=params).serve()``: the user's entry point."""
    import deepspeed_tpu as ds
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as workdir:
        cfg_path = os.path.join(workdir, "inference_config.json")
        with open(cfg_path, "w") as f:
            json.dump({"dtype": cell.system["dtype"],
                       "serving": cell.system["serving"]}, f)
        return ds.init_inference(model, cfg_path,
                                 model_parameters=params).serve(
            **({"interpret": True} if rehearsal else {}))


def submit_checked(srv, check: Dict[str, Any], vocab: int, seed: int) -> list:
    """The requests ``correct`` is decided on: seeded prompts of the cell's
    ``check.prompt_lens``, longest first, ``check.new_tokens`` greedy tokens
    each. A mixture's picks are asked for where the program hands them out
    to a request that asks (``submit(..., keep_routing=True)`` ->
    ``Request.routed_experts``): the reference then routes by them."""
    ask = ({"keep_routing": True} if "keep_routing" in
           inspect.signature(srv.submit).parameters else {})
    return [srv.submit(seeded_tokens(vocab, seed, 1000 + i, n),
                       max_new_tokens=int(check["new_tokens"]), **ask)
            for i, n in enumerate(sorted(check["prompt_lens"], reverse=True))]


def served_of(requests) -> List[Tuple[list, list, Optional[np.ndarray]]]:
    """``(prompt, served tokens, picks or None)`` of each finished request,
    as plain data: what the check needs once the engine is gone."""
    return [(list(r.prompt), list(r.output_tokens),
             getattr(r, "routed_experts", None)) for r in requests]


def check_served(family, cell: harness.Cell, params, served,
                 emitted: Optional[Sequence[Sequence[int]]] = None
                 ) -> Dict[str, Any]:
    """The plain reference over every checked request, one at a time:
    ``gaps`` (a request: how far each served token's reference logit lies
    under the reference's best, ``reference.served_token_gaps``) and
    ``deficits`` (a mixture whose program handed out its picks: how far each
    lies under the reference's own k-th best score; else empty). ``emitted``
    (the control): the tokens judged in the served ones' place."""
    check = cell.system["check"]
    # (params, ids) or, for a mixture's picks, (params, ids, picks)
    logits_fn = lambda p, *seq: family.reference_logits(cell.config, p, *seq)
    longest = max(check["prompt_lens"]) + int(check["new_tokens"])
    gaps, deficits = [], []
    for i, (prompt, tokens, picks) in enumerate(served):
        got = reference.served_token_gaps(
            logits_fn, params, prompt, tokens,
            reference.padded_len(len(prompt) + len(tokens), longest),
            picks=picks, emitted=None if emitted is None else emitted[i])
        if picks is not None:
            got, d = got
            deficits.append(d[np.asarray(picks) >= 0])
        gaps.append(got)
    return {"gaps": gaps, "deficits": deficits}


def judge(got: Dict[str, Any], picks_needed: bool
          ) -> Tuple[Dict[str, bool], Dict[str, Dict[str, float]]]:
    """``(checks, compared)`` of :func:`check_served`'s readings: what of
    them decides ``correct``, and each number compared beside its limit.
    ``picks_needed``: the mixture renormalises its picks' weights, so a
    reference that routes by itself compares another model (one flipped
    pick is 1/k of the routed branch: PERF.md, section 6, PR 42) and the
    check refuses a program that hands none out."""
    gaps = np.concatenate(got["gaps"])
    worst_gap, mean_gap = float(gaps.max()), float(gaps.mean())
    print(f"[serve] reference check: {int((gaps == 0).sum())} of {gaps.size} "
          f"served tokens are the reference's argmax; largest logit gap "
          f"{worst_gap:.4f} (margin {reference.SERVE_LOGIT_MARGIN}), mean "
          f"{mean_gap:.5f} (limit {reference.SERVE_MEAN_GAP_LIMIT})",
          flush=True)
    checks = {"served tokens within the margin of the reference's best":
              worst_gap <= reference.SERVE_LOGIT_MARGIN,
              "served tokens' mean gap within its limit":
              mean_gap <= reference.SERVE_MEAN_GAP_LIMIT}
    compared = {"served_logit_gap": {"value": worst_gap,
                                     "limit": reference.SERVE_LOGIT_MARGIN},
                "served_logit_gap_mean": {
                    "value": mean_gap,
                    "limit": reference.SERVE_MEAN_GAP_LIMIT}}
    if got["deficits"]:
        d = np.concatenate(got["deficits"])
        worst = float(d.max())
        print(f"[serve] picks: {int((d == 0).sum())} of {d.size} the "
              f"reference's own, largest deficit {worst:.4f} (tolerance "
              f"{reference.ROUTE_TIE_TOL})", flush=True)
        checks["every pick within the tie tolerance of the reference's "
               "scores"] = worst <= reference.ROUTE_TIE_TOL
        compared["pick_deficit"] = {"value": worst,
                                    "limit": reference.ROUTE_TIE_TOL}
    elif picks_needed:
        print("[serve] picks: the program hands none out (no keep_routing "
              "in submit) and the mixture renormalises its picks' weights: "
              "a reference that routes by itself cannot judge it", flush=True)
        checks["a renormalised mixture's program hands out its picks"] = False
    return checks, compared


class Client:
    """One outstanding request of the closed loop, and its stamps."""
    __slots__ = ("req", "max_new", "submitted", "seen", "last")

    def __init__(self, req, max_new, submitted):
        self.req, self.max_new, self.submitted = req, max_new, submitted
        self.seen, self.last = 0, None


def held_blocks(srv) -> int:
    """Distinct pool blocks held by the requests the engine is serving: its
    decode lanes and the prompt it is prefilling. Read from the engine's own
    lists, so it follows whatever the program reserves; ``BlockPool.
    used_count`` would add the blocks that only the prefix cache retains."""
    held = [s.blocks for s in srv._slots if s is not None]
    if srv._prefilling is not None:
        held.append(srv._prefilling.blocks)
    return len(set().union(*held))


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t0: float, trace_dir: str, rehearsal: bool = False
        ) -> Dict[str, Any]:
    devices = harness.take_devices(cell.chips, rehearsal)
    import jax

    if not rehearsal:
        harness.configure_compile_cache()
    watch = harness.CompileWatch(t0)
    watch.report("devices taken")

    from deepspeed_tpu.serving.scheduler import FAILED, FINISHED, SHED, \
        TIMEOUT

    watch.report("program imported")
    family = harness.load_family(cell.config["family"])
    mix, serving = cell.traffic, cell.system["serving"]
    model, mcfg, dtype = cell_model(cell, family)
    params = make_params(model, mcfg, seed, dtype)
    jax.block_until_ready(params)
    print(f"[serve] {cell.config['name']}: {mcfg.num_params() / 1e9:.3f}B "
          f"params in {cell.system['dtype']}; pool {serving['pool_blocks']} x "
          f"{serving['block_size']} tokens, {serving['max_batch']} lanes, "
          f"prefill chunk {serving.get('prefill_chunk_tokens', 0) or 'whole'}",
          flush=True)
    watch.report("weights")

    srv = start_engine(cell, model, params, rehearsal)
    watch.report("engine")
    bs, usable = srv.block_size, srv.pool.num_blocks - 1
    vocab = mcfg.vocab_size

    # ---- warm-up: the decode program, every prefill shape, the checked
    # requests (served here, held against the reference behind the window)
    chunk = int(serving.get("prefill_chunk_tokens", 0))
    longest = int(mix["prompt_len"]["max"])
    shapes = range(bs, (min(chunk, longest) if chunk else longest) + 1, bs)
    # the checked requests first, longest first: their decode steps then
    # run beside the other shapes' prefills and warm-up is over sooner
    checked = submit_checked(srv, cell.system["check"], vocab, seed)
    warm = [srv.submit(seeded_tokens(vocab, seed, i, n), max_new_tokens=2)
            for i, n in enumerate(shapes)]
    srv.run_until_idle()
    warm_ok = all(r.state == FINISHED for r in warm + checked)
    served = served_of(checked)
    del warm, checked
    watch.report("warm-up")

    # ---- the closed loop ---------------------------------------------------
    stream = request_stream(mix, vocab, seed)
    clients: List[Client] = []
    tally = {"attempted": 0, "failed": 0, "first_tokens": 0}
    ttft: List[float] = []            # submit -> first token, window requests
    itl: List[float] = []             # gaps between tokens inside the window
    win = {"start": None, "end": None, "tokens": 0}

    def submit() -> Client:
        prompt, max_new = next(stream)
        with tracing.annotate("submit"):
            now = time.perf_counter()
            req = srv.submit(prompt, max_new_tokens=max_new)
        tally["attempted"] += 1
        return Client(req, max_new, now)

    def in_window(t) -> bool:
        return win["start"] is not None and t >= win["start"] and \
            (win["end"] is None or t <= win["end"])

    def after_step(now: float) -> None:
        """Stamp the tokens the last step produced; replace what finished."""
        for i, c in enumerate(clients):
            n = len(c.req.output_tokens)
            if n > c.seen:
                if c.seen == 0:
                    tally["first_tokens"] += 1
                    if in_window(c.submitted):
                        ttft.append(now - c.submitted)
                elif in_window(c.last) and in_window(now):
                    itl.append(now - c.last)
                if in_window(now):
                    win["tokens"] += n - c.seen
                c.seen, c.last = n, now
            if c.req.state in (FINISHED, FAILED, TIMEOUT, SHED):
                if c.req.state != FINISHED or n != c.max_new:
                    tally["failed"] += 1
                if win["end"] is None:
                    clients[i] = submit()

    def loop(until) -> Dict[str, Any]:
        """``srv.step()`` until ``until()``; what the steps showed."""
        o = {"decode_step": [], "prefill_step": [], "lane_sum": 0,
             "slot_sum": 0, "held_sum": 0, "held_peak": 0, "steps": 0}
        while not until():
            pre = srv.stats["prefill_tokens"]
            held = held_blocks(srv)
            lanes = srv.active
            with tracing.annotate("step"):
                t = time.perf_counter()
                srv.step()                # ends in the fetch of the tokens
                now = time.perf_counter()
            o["prefill_step" if srv.stats["prefill_tokens"] > pre
              else "decode_step"].append(now - t)
            o["lane_sum"] += lanes
            o["slot_sum"] += srv.max_batch
            o["held_sum"] += held
            o["held_peak"] = max(o["held_peak"], held)
            o["steps"] += 1
            with tracing.annotate("client"):
                after_step(now)
        return o

    clients.extend(submit() for _ in range(int(mix["clients"])))
    n_clients = len(clients)
    loop(lambda: tally["first_tokens"] >= n_clients)
    watch.report("ramp")

    counters: Dict[str, float] = {}
    the_trace = None
    compiles_before = watch.compiles
    setup_s = time.perf_counter() - t0
    if trace:
        trace_s = min(harness.TRACE_SECONDS, seconds / 2)
        trace_end = time.perf_counter() + trace_s
        o, the_trace = tracing.record(
            trace_dir, lambda: loop(lambda: time.perf_counter() >= trace_end))
        counters["traced_steps"] = o["steps"]
        seconds = max(seconds - trace_s, 1.0)
    attempted_before = tally["attempted"]
    failed_before = tally["failed"]
    win["start"] = time.perf_counter()
    o = loop(lambda: time.perf_counter() - win["start"] >= seconds)
    win["end"] = time.perf_counter()
    compiles_in_window = watch.compiles - compiles_before
    # to the first token of every request submitted inside the window
    limit = win["end"] + DRAIN_LIMIT_S
    loop(lambda: time.perf_counter() > limit or all(
        c.seen > 0 or not in_window(c.submitted) for c in clients))
    late = sum(1 for c in clients if c.seen == 0 and in_window(c.submitted))
    srv.close()
    watch.report("window")

    wall = win["end"] - win["start"]
    attempted = tally["attempted"] - attempted_before
    failed = tally["failed"] - failed_before + late
    counters.update(lane_sum=o["lane_sum"], slot_sum=o["slot_sum"],
                    held_sum=o["held_sum"], usable_sum=usable * o["steps"],
                    held_peak=o["held_peak"], usable_blocks=usable)
    print(f"[serve] window {wall:.2f}s: {o['steps']} steps "
          f"({len(o['prefill_step'])} with a prefill), {win['tokens']} "
          f"tokens, {attempted} requests submitted, {len(ttft)} first tokens,"
          f" {len(itl)} token gaps, {failed} failed; compiles inside the "
          f"window: {compiles_in_window}; stats {srv.stats}", flush=True)

    # ---- the check: the plain reference over the checked requests, with
    # the peak read and the engine (its pool, its programs' buffers) dropped
    one_decode_program = srv._decode_fn._cache_size() == 1
    memory_peak = harness.memory_peak_bytes(devices)
    srv = None
    clients.clear()
    gc.collect()
    got = check_served(family, cell, params, served)
    watch.report("reference check")

    end_to_end = {
        "serve_tokens_per_s": {"value": win["tokens"] / wall,
                               "unit": "tokens/s"},
        "itl_p95_ms": {"value": 1e3 * reduce.p95(itl), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"}}
    checks = {"warm-up and checked requests finished": warm_ok}
    judged, compared = judge(got, picks_needed=bool(
        mcfg.moe_experts and mcfg.moe_norm_topk))
    checks.update(judged)
    checks.update({
        "no request failed": failed == 0,
        "one decode program": one_decode_program,
        "no compile inside the window": compiles_in_window == 0})
    obs = {"clocks": {"decode_step": o["decode_step"],
                      "prefill_step": o["prefill_step"], "ttft": ttft},
           "counters": counters, "trace": the_trace,
           "context": harness.context(cell, family, devices, rehearsal)}
    return {"checks": checks, "compared": compared, "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end, "obs": obs,
            "devices": devices, "memory_peak": memory_peak}
