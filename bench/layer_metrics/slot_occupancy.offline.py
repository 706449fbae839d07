"""Scheduler: mean over the window's ticks of the slots that took part in
the tick (a prefill chunk or a decode row), as a share of all slots."""


def read(run):
    if not run.ticks:
        return None
    return 100.0 * sum(len(t["rows"]) for t in run.ticks) / (
        len(run.ticks) * run.slots)
