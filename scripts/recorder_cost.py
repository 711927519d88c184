#!/usr/bin/env python3
"""What the recorder costs a serving step, on the host alone (no device, no
model): one ``srv.step()``'s spans, events and counter gains replayed through
a ``telemetry.Recorder`` as ``serving/engine.py`` makes them, against the same
loop through a recorder whose spans are no-ops.

    python scripts/recorder_cost.py [--steps 20000]

A step with a prefill chunk is the longest the loop makes (a request admitted,
a final chunk fetched and installed, one lane finished): 19 spans, 3 events,
a step span that copies and diffs the counters (and adds its own time to
``serve.step_us``), two of the spans waits (``serve.wait_us``). A decode-only
step is 9 spans. The recorder has no switch, so this is in every end-to-end number; the
number here is a CPU's, the chip's host is read from a pair of untraced runs
(PERF.md, section 6, PR 38).
"""

import argparse
import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.utils import telemetry  # noqa: E402

#: the counters a serving engine of a dropless mixture holds after a start
#: its cache served (PR 56: 63)
COUNTERS = (
    "completed failed timeout tokens_generated prefill_tokens "
    "prefix_hit_tokens preempted steps steps_with_queue queue_len_sum "
    "lane_sum admit_blocked.no_lane admit_blocked.no_blocks "
    "admit_blocked.prefilling compiles kv.held_blocks_sum "
    "kv.blocks_reserved_sum kv.tokens_written_sum prefix.prompt_tokens "
    "paged.live_pages_sum paged.table_pages_sum paged.window_pages_sum "
    "paged.chunk_live_pages_sum paged.chunk_table_pages_sum "
    "paged.chunk_turns_sum paged.chunk_key_tiles_sum "
    "paged.chunk_key_tiles_live_sum step_inputs.transfers_sum "
    "step_inputs.lane_rows_written_sum decode_ahead.launched "
    "decode_ahead.device_lane_tokens_sum decode_ahead.wasted_lane_tokens "
    "decode_ahead.retired_unread kv.alloc kv.release kv.exhausted "
    "prefix.lookups prefix.inserted_entries prefix.evicted_entries "
    "prefix.evict_scanned_entries moe.assignments moe.held_assignments "
    "moe.layer_steps moe.load_max_over_mean_sum moe.experts_idle_sum "
    "routing.fetches compile.trace_us compile.lower_us compile.backend_us "
    "compile.cache_load_us compile.cache_requests compile.cache_hits "
    "compile.cache_misses compile.backend_compiles compile.saved_us "
    "compile.first_call_rest_us compile.first_calls serve.init_us "
    "serve.step_us serve.wait_us").split()


class Stub:
    """A recorder that records nothing (what the chip pair stubs in)."""

    def __init__(self):
        self.counters = {}
        self.gauges = {}
        self._null = contextlib.nullcontext()

    def span(self, name, **attrs):
        return self._null

    step_span = span

    def event(self, name, **attrs):
        pass

    def count(self, name, n=1):
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name, value):
        self.gauges[name] = value


def one_step(rec, step: int, chunk: bool) -> None:
    """The recorder calls of one ``ServingEngine.step()``."""
    c = rec.counters
    with rec.step_span("serve.step", step=step):
        c["steps"] += 1
        c["steps_with_queue"] += 1
        c["queue_len_sum"] += 3
        c["lane_sum"] += 28
        c["kv.held_blocks_sum"] += 320
        c["kv.blocks_reserved_sum"] += 320
        c["kv.tokens_written_sum"] += 8000
        with rec.span("serve.admit"):
            with rec.span("serve.shed"):
                pass
            if chunk:
                with rec.span("serve.admit.peek"):
                    pass
                with rec.span("serve.admit.evict"):
                    c["prefix.evicted_entries"] += 4
                with rec.span("serve.admit.alloc", rid=step):
                    c["kv.alloc"] += 9
                rec.event("serve.req.admitted", rid=step, arrival_ts=1.0,
                          ts=2.0)
            else:
                c["admit_blocked.prefilling"] += 1
        if chunk:
            with rec.span("serve.prefill", rid=step, tokens=256, final=1):
                with rec.span("serve.prefill.build"):
                    rec.count("paged.chunk_live_pages_sum", 8)
                    rec.count("paged.chunk_table_pages_sum", 40)
                with rec.span("serve.prefill.dispatch",
                              program="jit__prefill"):
                    c["step_inputs.transfers_sum"] += 1
                with rec.span("serve.prefill.install"):
                    c["step_inputs.lane_rows_written_sum"] += 1
        with rec.span("serve.decode", lanes=28):
            with rec.span("serve.decode.build"):
                rec.count("paged.live_pages_sum", 277)
                rec.count("paged.table_pages_sum", 1280)
                rec.count("decode_ahead.device_lane_tokens_sum", 28)
            with rec.span("serve.decode.dispatch", program="jit__decode"):
                c["step_inputs.transfers_sum"] += 1
            c["decode_ahead.launched"] += 1
            if chunk:
                # the chunk's first token, behind the launch (PR 40)
                with rec.span("serve.prefill.fetch"):
                    pass
                rec.event("serve.req.first_token", rid=step, ts=3.0)
                with rec.span("serve.prefill.prefix_insert"):
                    c["prefix.inserted_entries"] += 8
            with rec.span("serve.decode.fetch"):
                pass
            with rec.span("serve.decode.bookkeep"):
                c["tokens_generated"] += 28
                if chunk:
                    c["completed"] += 1
                    rec.event("serve.req.finished", rid=step, ts=4.0)
        with rec.span("serve.heartbeat"):
            pass


def us_a_step(rec, steps: int, chunk: bool) -> float:
    rec.counters.update(dict.fromkeys(COUNTERS, 0))
    for i in range(2000):                      # warm the interpreter's caches
        one_step(rec, i, chunk)
    t = time.perf_counter()
    for i in range(steps):
        one_step(rec, i, chunk)
    return (time.perf_counter() - t) / steps * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=20000)
    args = ap.parse_args()
    for label, chunk in (("decode-only step", False),
                         ("step with a final chunk", True)):
        # best of five: the loop is short and the machine is shared
        on = min(us_a_step(telemetry.Recorder("serve", keep=False),
                           args.steps, chunk) for _ in range(5))
        off = min(us_a_step(Stub(), args.steps, chunk) for _ in range(5))
        print(f"recorder_cost: {label}: {on:.1f} us with the recorder, "
              f"{off:.1f} us with its spans stubbed: {on - off:.1f} us "
              f"a step", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
