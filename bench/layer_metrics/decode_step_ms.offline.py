"""Model step: mean device busy time of a traced decode tick, in ms."""
from bench import trace_reduce


def read(run):
    spans = [s for t, s in run.traced_ticks if t["kind"] == "decode"]
    if not spans:
        return None
    return sum(trace_reduce.busy_ns(run.trace["ops"], a, b)
               for a, b in spans) / len(spans) / 1e6
