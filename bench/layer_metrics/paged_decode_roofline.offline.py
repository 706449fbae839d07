"""Kernels (kernels/decode/decode.py, the fused paged decode): the least
time the chip needs for the kernel's work (bench/costs, per layer call)
over the kernel's device time, across the traced decode ticks."""
from bench import costs, trace_reduce

KERNEL = "paged_decode_fwd_pallas"


def read(run):
    need = spent = 0.0
    for t, (a, b) in run.traced_ticks:
        if t["kind"] != "decode":
            continue
        calls = trace_reduce.ops_in(run.trace["ops"], a, b, KERNEL)
        spent += sum(d for _, _, d in calls) / 1e9
        work = costs.attention_kernel_cost(run.sz, t["rows"],
                                           costs.cache_dtype(run.cell.config))
        need += run.sz["layers"] * costs.roofline_seconds(*work, run.peak)
    return 100.0 * need / spent if spent else None
