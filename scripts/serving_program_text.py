#!/usr/bin/env python3
"""The serving loop's device programs as text, to compare two trees without
the chip: ``serving.engine.step_programs``' decode and prefill step for the
benchmark's serving configurations (a latent cache's prefill step at every
256-row tile up to its chunk, the others' mixed step: the programs their
engines make), lowered for a described TPU v5e from
``jax.ShapeDtypeStruct``s at the cells' shapes, with every source location
stripped. Two trees whose texts are equal hand the chip's compiler the same
programs; a refactor of ``paged_forward`` or of the layer under it is held
to that (PERF.md section 6, PR 30).

Run it from the root of each tree and compare the directories:

    python scripts/serving_program_text.py --out /root/scratch/text/change
    (cd <parent checkout> && PYTHONPATH=. python \
        /root/repo/scripts/serving_program_text.py --out /root/scratch/text/parent)
    diff -r /root/scratch/text/parent /root/scratch/text/change

Locations: ``as_text(debug_info=False)`` drops the program's own; a Mosaic
kernel's serialized body keeps the line numbers of its call sites whatever
that flag says (PERF.md, PR 27), so the body's debug info is stripped before
jax serializes it (the one patch below; only this script's process has it).

gpt2-1.3b has no serving cell: it takes ``chip_smoke.py``'s serving widths,
and its int8 tier (int8 KV pool, blockwise-int8 weights) as a third and
fourth program. Nothing runs and nothing is compiled here.
"""

import argparse
import hashlib
import os
import sys

sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src import tpu_custom_call  # noqa: E402

#: gpt2-1.3b: chip_smoke.FULL's serving section and its longest prefill bucket
SMOKE_SERVING = {"block_size": 32, "pool_blocks": 1024, "max_batch": 8,
                 "max_blocks_per_seq": 32, "prefill_chunk_tokens": 256}
CELLS = {"mistral-7b-l16": "serve-mistral-7b-l16-chat",
         "olmoe-1b-7b-l8": "serve-olmoe-1b-7b-l8-gen",
         "k-exaone-236b-ep8-l5": "serve-k-exaone-236b-ep8-l5-mixed",
         "keye-vl2-30b-ep8-l8": "serve-keye-vl2-30b-ep8-l8-longdoc",
         "deepseek-v2-ep8-l5": "serve-deepseek-v2-ep8-l5-longdoc",
         "deepseek-v32-exp-ep16-l5": "serve-deepseek-v32-exp-ep16-l5-longdoc"}


def _strip_kernel_locations():
    inner = tpu_custom_call._lower_mosaic_module_to_asm

    def stripped(module, **kw):
        with module.context:
            tpu_custom_call.PassManager.parse(
                "builtin.module(strip-debuginfo)").run(module.operation)
        return inner(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = stripped


def programs(name: str, config, serving, *, int8: bool, chip):
    """``(label, lowered text)`` of the decode and the prefill step."""
    from benchmark import harness
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.ops.pallas.quant_matmul import pack_decode_weights
    from deepspeed_tpu.serving.engine import (StepLayout, step_programs,
                                              token_words)
    from deepspeed_tpu.serving.kv_cache import init_pool

    family = harness.load_family(config["family"])
    model, cfg = build_model(TransformerConfig(
        **family.model_kwargs(config), dtype=jnp.bfloat16))
    bs, lanes = serving["block_size"], serving["max_batch"]
    nbk, chunk = serving["max_blocks_per_seq"], serving["prefill_chunk_tokens"]

    def weights():
        p = ensure_scan_layout(jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), model.init(
                jax.random.PRNGKey(0),
                {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]),
            cfg.num_layers)
        return pack_decode_weights(p) if int8 else p

    on_chip = lambda tree: jax.tree.map(lambda x: chip(x.shape, x.dtype), tree)
    params = on_chip(jax.eval_shape(weights))
    pools = on_chip(jax.eval_shape(lambda: init_pool(
        cfg, serving["pool_blocks"], bs,
        jnp.int8 if int8 else jnp.bfloat16)))
    decode, prefill, *mixed = step_programs(cfg, bs, nbk,
                                            mixed=not cfg.kv_lora_rank)
    layout = StepLayout(nbk)
    # the decode program reads, beside its buffer, the previous decode
    # call's and the last final prefill call's outputs where they lie; the
    # mixed program (a chunk and the lanes in one, where the cache is not
    # latent) takes the decode program's operands
    fed = [token_words(cfg, lanes), token_words(cfg, 1)]
    calls = {"decode": (decode, layout.decode_words(lanes), fed)}
    # a latent cache's engine brings its prefill calls in whole 256-row
    # tiles (``ServingEngine._prefill_rows``): every program a chunked
    # engine of the cell makes; the others' largest
    for rows in range(256, chunk + 1, 256) if cfg.kv_lora_rank else [chunk]:
        calls[f"prefill{rows}"] = (prefill, layout.prefill_words(rows), [])
    for fn in mixed:
        calls[f"mixed{chunk}"] = (fn, layout.prefill_words(chunk)
                                  + layout.decode_words(lanes), fed)
    for label, (fn, words, fed) in calls.items():
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pools, *(chip((n,), jnp.int32) for n in [words] + fed))
        yield (f"{name}{'-int8' if int8 else ''}.{label}",
               lowered.as_text(debug_info=False))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="directory for the texts")
    args = ap.parse_args()
    from benchmark import harness
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # the dispatch wrappers ask the default backend which kernel to take
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    _strip_kernel_locations()
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)
    gpt2 = harness.load_json(os.path.join(harness.HERE, "configs",
                                          "gpt2-1.3b.json"))
    todo = [("gpt2-1.3b", gpt2, SMOKE_SERVING, False),
            ("gpt2-1.3b", gpt2, SMOKE_SERVING, True)]
    for name, cell_name in CELLS.items():
        try:
            cell = harness.load_cell(cell_name)
        except FileNotFoundError:       # a tree from before the cell
            print(f"no cell {cell_name} in this tree", flush=True)
            continue
        todo.append((name, cell.config, cell.system["serving"], False))
    os.makedirs(args.out, exist_ok=True)
    for name, config, serving, int8 in todo:
        for label, text in programs(name, config, serving, int8=int8,
                                    chip=chip):
            with open(os.path.join(args.out, label + ".mlir"), "w") as f:
                f.write(text)
            print(f"{hashlib.sha256(text.encode()).hexdigest()[:16]}  "
                  f"{len(text.splitlines()):6d} lines  {label}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
