"""Scheduler (serve/engine.py admission): 95th percentile, over the
requests due in the window, of the wait from the due instant to the
engine's admission stamp ``Request.admit_time`` (host clock)."""
import numpy as np


def read(run):
    waits = [(t.req.admit_time - t.due) * 1e3 for t in run.tracks
             if t.req.admit_time is not None]
    return float(np.percentile(waits, 95)) if waits else None
