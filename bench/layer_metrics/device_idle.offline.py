"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window; busy is the union of op intervals)."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace["window"]
    return 100.0 * (1.0 - trace_reduce.busy_ns(run.trace["ops"], lo, hi)
                    / (hi - lo))
