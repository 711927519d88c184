"""The paged kernel alone, on the chip, at the serving cells' shapes (and at
one shape with heads narrower than 128 lanes, which take the grid kernel).

    chiprun -- python scripts/paged_kernel_bench.py [--pages 1,2,4,8]
        [--old FILE[,FILE]] [--shapes NAME[,NAME]] [--chunks-only]

One "step" is what a decode program asks of the kernel: one call a layer
on the stacked pool, every lane at its own context length (lognormal round
the cell's mean, some lanes idle at the one token the engine gives them).
Prints a JSON line a shape and setting: ms a step, the bytes the live pages
hold (K and V, every STORED head, read from the pool's own shape: a
grouped-query model's pool holds its KV heads since PR 44; a layer with a
sliding window walks its window's pages only), and that over the chip's
819 GB/s as a share of the time: the kernel's roofline share. ``--pages``
overrides the pages a copy group holds (the program derives it from the
shapes: ``_pages_per_group``), for the chunk rows too; ``--old FILE[,FILE]``
times other versions of the kernel's module (e.g. ``git show <commit>:
deepspeed_tpu/ops/pallas/paged_attention.py``) on the same queries and
keys; ``--old-expanded`` gives those the pool as it was stored before
PR 44, a row a QUERY head (its bytes counted as such). Parity against the
jnp reference is checked on the device before anything is timed. Needs a
TPU.

Since PR 37 the kernel takes a prefill chunk's T > 1 query rows a lane:
after the decode rows, one row a chunk shape of :data:`CHUNKS` at the
cells' widths (one lane, as the serving loop prefills): ms for the
layers' calls, the bytes of the pages walked and the FLOPs of the causal
scores and values, each over the chip's peak as a share of the time (the
larger is the kernel's roofline share and ``bound`` says which), the heads
a program and pages a group the shapes gave, and the jnp reference's time
on the same inputs (what the prefill programs ran until then). The
long-document cell (PR 51) takes :data:`LONG_CHUNKS`: 256 rows first, behind
5 120 and behind 24 320 cached keys, each without and with a learned
indexer's selection (:func:`selection`).
"""

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

HBM_GBPS = 819.0     # TPU v5e, Google Cloud "TPU v5e"; benchmark/peaks.json
BF16_TFLOPS = 197.0

#: (query rows, first position): a prompt's first chunk at the smallest and
#: the largest shape, and a late chunk of a long prompt
CHUNKS = ((32, 0), (256, 0), (256, 768))
#: the long-document cell's: a 256-row chunk first, behind 5 120 cached keys
#: (the window's mean) and behind 24 320 (the longest prompt's last chunk),
#: each without and with a learned indexer's selection (``select=``: seeded
#: index scores, every row its exact top ``INDEX_TOPK``)
LONG_CHUNKS = ((256, 0), (256, 5120), (256, 24320))
INDEX_TOPK = 2048

# layers, query heads, stored (KV) heads, head_dim, block, pool blocks, lanes,
# table, live lanes, mean context of a live lane (PERF.md section 5: ~58 k
# tokens over 52 lanes; ~260 pages over 28 lanes; ~510 over 24), the layers'
# sliding windows (one for all, or one a layer; 0: none)
SHAPES = {
    "serve-olmoe-1b-7b-l8-gen": (8, 16, 16, 128, 32, 2048, 64, 128, 52, 1100,
                                 0),
    "serve-mistral-7b-l16-chat": (16, 32, 8, 128, 32, 384, 32, 40, 28, 290,
                                  4096),
    "serve-k-exaone-236b-ep8-l5-mixed": (5, 64, 8, 128, 32, 1024, 32, 128, 24,
                                         680, (128, 128, 128, 0, 128)),
    # its chunk rows are LONG_CHUNKS, each also under a selection
    "serve-keye-vl2-30b-ep8-l8-longdoc": (8, 32, 4, 128, 32, 8192, 16, 800,
                                          12, 9000, 0),
    # no cell: heads of 64 take the grid kernel (a grid step a table entry)
    "llama-1.1b": (22, 32, 4, 64, 32, 512, 16, 64, 14, 400, 0),
}


def walked_pages(ctx, q0, bs, windows):
    """Pages the kernel walks for one lane, summed over the layers: from
    the page of the first query's window to the last real query's."""
    last = -(-ctx // bs)
    return sum(last - (max(q0 + 1 - w, 0) // bs if w else 0)
               for w in windows)


def best_ms(fn, reps, *operands):
    """(the least ms a call over three rounds of ``reps`` back-to-back
    calls, the last result); the first call, which compiles, is not timed."""
    fn(*operands).block_until_ready()
    best = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*operands)
        out.block_until_ready()
        best.append((time.perf_counter() - t) / reps)
    return min(best) * 1e3, out


def load(path):
    if path is None:
        from deepspeed_tpu.ops.pallas import paged_attention as mod
        return mod
    spec = importlib.util.spec_from_file_location(
        f"deepspeed_tpu.ops.pallas._other_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", default="")
    ap.add_argument("--old", default="")
    ap.add_argument("--old-expanded", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--shapes", default="",
                    help="comma-separated names of SHAPES (all by default)")
    ap.add_argument("--chunks-only", action="store_true",
                    help="skip the decode rows")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("paged_kernel_bench needs a TPU")
    new = load(None)
    olds = [load(path) for path in args.old.split(",") if path]
    derive = new._pages_per_group
    settings = [("derived", new, None)]
    settings += [(f"pages={p}", new, int(p))
                 for p in args.pages.split(",") if p]
    settings += [(f"old:{Path(mod.__file__).name}", mod, None)
                 for mod in olds]
    for name, (L, nh, kvh, hd, bs, nb, B, nbk, live, mean, window) in \
            SHAPES.items():
        if args.shapes and name not in args.shapes.split(","):
            continue
        windows = (window,) * L if isinstance(window, int) else window
        window = jnp.asarray(windows, jnp.int32)
        rng = np.random.default_rng(args.seed)
        ctx = np.ones((B,), np.int32)
        ctx[:live] = np.clip(rng.lognormal(np.log(mean) - 0.32, 0.8, live),
                             1, nbk * bs).astype(np.int32)
        pages = -(-ctx // bs)
        while pages.sum() > nb - 1:          # the pool holds what it holds
            ctx[np.argmax(ctx)] //= 2
            pages = -(-ctx // bs)
        rng.shuffle(ctx)
        pages = -(-ctx // bs)
        bt = np.zeros((B, nbk), np.int32)
        free = rng.permutation(nb - 1) + 1
        at = 0
        for b in range(B):
            bt[b, :pages[b]] = free[at:at + pages[b]]
            at += pages[b]
        key = jax.random.PRNGKey(args.seed)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, nh, 1, hd), jnp.bfloat16)
        kp = jax.random.normal(kk, (L, kvh, nb, bs, hd), jnp.bfloat16)
        vp = jax.random.normal(kv, (L, kvh, nb, bs, hd), jnp.bfloat16)
        bt_d, ctx_d = jnp.asarray(bt), jnp.asarray(ctx)
        walked = sum(walked_pages(int(c), int(c) - 1, bs, windows)
                     for c in ctx)
        ref = new.paged_attention_reference(
            q, kp, vp, bt_d, ctx_d, layer_idx=jnp.int32(L - 1),
            window=window[L - 1])
        stored = expanded = (kp, vp)
        if olds and args.old_expanded and nh != kvh:
            expanded = tuple(jnp.repeat(p, nh // kvh, axis=1)
                             for p in stored)
        pools_of = lambda mod: expanded if mod in olds else stored
        for label, mod, force in ([] if args.chunks_only else settings):
            kp, vp = pools_of(mod)
            need = walked * kp.shape[1] * bs * hd * 2 * 2
            new._pages_per_group = (
                derive if force is None else
                lambda *a, _p=force, **k: min(_p, a[4]))

            def step(q, kp, vp, bt, ctx, mod=mod):
                def layer(acc, li):
                    o = mod.paged_attention(q, kp, vp, bt, ctx, layer_idx=li,
                                            window=window[li])
                    return acc + o.astype(jnp.float32), None
                return jax.lax.scan(
                    layer, jnp.zeros(q.shape, jnp.float32), jnp.arange(L))[0]

            one = jax.jit(lambda q, kp, vp, bt, ctx, mod=mod:
                          mod.paged_attention(q, kp, vp, bt, ctx,
                                              layer_idx=jnp.int32(L - 1),
                                              window=window[L - 1]))
            err = float(jnp.max(jnp.abs(
                one(q, kp, vp, bt_d, ctx_d).astype(jnp.float32)
                - ref.astype(jnp.float32))))
            ms, _ = best_ms(jax.jit(step), args.reps, q, kp, vp, bt_d, ctx_d)
            P = new._pages_per_group(
                int(np.prod(new._program_heads(nh, kvh, bs, hd, 2))), bs, hd,
                2, nbk) if mod is new and hd % 128 == 0 else None
            print(json.dumps({
                "shape": name, "setting": label, "pages_per_group": P,
                "stored_heads": kp.shape[1], "query_heads": nh,
                "device_kind": dev.device_kind, "ms_per_step": ms,
                "live_pages_a_layer": int(pages.sum()),
                "walked_pages_a_step": walked,
                "table_pages_a_layer": B * nbk,
                "live_share": float(pages.sum() / (B * nbk)),
                "needed_gb": need / 1e9,
                "roofline_pct": 100 * need / (HBM_GBPS * 1e9) / (ms / 1e3),
                "max_abs_err_vs_reference": err}), flush=True)
        new._pages_per_group = derive
        if hd % 128 == 0:
            for label, mod, force in settings:
                if force is not None:
                    mod._pages_per_group = (
                        lambda *a, _p=force, **k: min(_p, a[4]))
                chunk_rows(mod, name, label, dev, args, L, nh, hd, bs, nb,
                           nbk, windows, *pools_of(mod))
                new._pages_per_group = derive


def selection(T, q0, Kp, seed):
    """Seeded index scores ``[1, T, Kp]`` (``-inf`` on the keys a row does
    not see) and every row's EXACT top ``INDEX_TOPK``: the threshold is its
    k-th best score (``-inf`` while it sees no more than k), ties to the
    lower position (seeded floats have none)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.sparse_select import Selection
    sc = jax.random.normal(jax.random.PRNGKey(seed), (1, T, Kp), jnp.float32)
    seen = jnp.arange(Kp)[None, None] <= (q0 + jnp.arange(T))[None, :, None]
    sc = jnp.where(seen, sc, -jnp.inf)
    thr = jax.lax.top_k(sc, INDEX_TOPK)[0][..., -1]
    return Selection(sc, thr, jnp.full((1, T), Kp, jnp.int32))


def chunk_rows(mod, name, label, dev, args, L, nh, hd, bs, nb, nbk, windows,
               kp, vp):
    """One line a chunk shape: the kernel at T > 1 against the reference
    (one layer's parity before anything is timed)."""
    import jax
    import jax.numpy as jnp
    jax.clear_caches()      # the chunk's call is a jit of its own: a forced
    rng = np.random.default_rng(args.seed + 1)     # P has to be traced anew
    long = "longdoc" in name
    cases = [(T, q0, sel) for T, q0 in (LONG_CHUNKS if long else CHUNKS)
             for sel in ((False, True) if long else (False,))]
    for T, q0, sel in cases:
        ctx = min(q0 + T, nbk * bs)
        pages = -(-ctx // bs)
        bt = np.zeros((1, nbk), np.int32)
        bt[0, :pages] = rng.permutation(nb - 1)[:pages] + 1
        q = jax.random.normal(jax.random.PRNGKey(args.seed + T),
                              (1, nh, T, hd), jnp.bfloat16)
        bt_d = jnp.asarray(bt)
        ctx_d, q0_d = jnp.asarray([ctx], jnp.int32), jnp.asarray([q0],
                                                                 jnp.int32)
        window = jnp.asarray(windows, jnp.int32)
        select = selection(T, q0, -(-nbk * bs // 128) * 128,
                           args.seed + q0) if sel else None

        def layers(fn, layers=L):
            def step(q, kp, vp, select):
                def layer(acc, li):
                    o = fn(q, kp, vp, bt_d, ctx_d, layer_idx=li,
                           window=window[li], q_start=q0_d, select=select)
                    return acc + o.astype(jnp.float32), None
                return jax.lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                                    L - 1 - jnp.arange(layers))[0]
            return jax.jit(step)

        n = ctx - q0                                     # the real rows
        operands = (q, kp, vp, select)
        one = layers(mod.paged_attention, 1)(*operands)
        ref = layers(mod.paged_attention_reference, 1)(*operands)
        err = float(jnp.max(jnp.abs(one[:, :, :n] - ref[:, :, :n])))
        ms, _ = best_ms(layers(mod.paged_attention), args.reps, *operands)
        # the gather reference holds [heads, rows, keys] float32 scores: a
        # layer of the long table is 0.8 GB, and nobody serves through it
        ref_ms = None if long else best_ms(
            layers(mod.paged_attention_reference), args.reps, *operands)[0]
        walked = walked_pages(ctx, q0, bs, windows)
        need = walked * kp.shape[1] * bs * hd * 2 * 2
        # a real row at position p sees p + 1 keys (its window's at most,
        # its selection's at most): QK^T and PV, 2 FLOPs each
        keys = sum(min(q0 + r + 1, w or ctx, INDEX_TOPK if sel else ctx)
                   for r in range(n) for w in windows)
        flops = 4 * keys * hd * nh
        mem, mxu = need / (HBM_GBPS * 1e9), flops / (BF16_TFLOPS * 1e12)
        plan = {}
        if hasattr(mod, "_program_heads"):
            hg, gq = mod._program_heads(nh, kp.shape[1], bs, hd, 2, T)
            plan = {"stored_heads_per_program": hg,
                    "query_heads_of_a_stored_head_per_program": gq,
                    "pages_per_group": mod._pages_per_group(
                        hg * gq, bs, hd, 2, nbk, False, T)}
        print(json.dumps({
            "shape": name, "setting": f"chunk T={T} q0={q0}"
            + (" select" if sel else "") + " " + label,
            "stored_heads": kp.shape[1], "query_heads": nh, **plan,
            "device_kind": dev.device_kind, "ms_per_step": ms,
            "us_per_layer": 1e3 * ms / L, "reference_ms_per_step": ref_ms,
            "pages_walked_a_step": walked, "needed_gb": need / 1e9,
            "needed_gflop": flops / 1e9,
            "bound": "memory" if mem >= mxu else "compute",
            "memory_roofline_pct": 100 * mem / (ms / 1e3),
            "compute_roofline_pct": 100 * mxu / (ms / 1e3),
            "max_abs_err_of_a_layer_vs_reference": err}), flush=True)


if __name__ == "__main__":
    main()
