"""Model step (models/api.prefill_paged): useful operations of the traced
prefill ticks (the architecture's ``step_flops``) over their device busy
time at the chip's peak."""
from bench import trace_reduce


def read(run):
    pairs = [(t, s) for t, s in run.traced_ticks if t["kind"] == "prefill"]
    if not pairs:
        return None
    flops = sum(run.cell.arch.step_flops(run.sz, t["rows"]) for t, _ in pairs)
    busy = sum(trace_reduce.busy_ns(run.trace["ops"], a, b)
               for _, (a, b) in pairs) / 1e9
    return 100.0 * flops / (busy * run.peak["bf16_flops_per_s"])
