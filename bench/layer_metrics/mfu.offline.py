"""Model step, whole window: useful operations of every traced tick over
the traced window's length at the chip's peak (idle time included)."""


def read(run):
    if not run.traced_ticks:
        return None
    lo, hi = run.trace["window"]
    flops = sum(run.cell.arch.step_flops(run.sz, t["rows"])
                for t, _ in run.traced_ticks)
    return 100.0 * flops / ((hi - lo) / 1e9 * run.peak["bf16_flops_per_s"])
