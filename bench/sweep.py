"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow.

    python bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \
        --seconds 60 --seed <n>

One process on the chip builds the cell's engine once and steps the
offered rate upward, ``--seconds`` per rate, each rate starting from the
occupancy the previous one left. Per rate it prints one JSON line: the
requests due, quartiles and tails (ms) of TTFT and of the inter-token
gaps (TPOT), the queue wait in the first and second half of the window,
the requests still unadmitted when the window closes, and the mean share
of slots busy. The backlog grows where the second half's queue wait
keeps climbing and the queue does not empty.
The knee found is recorded in PERF.md and the cell's rate set from it;
the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import run, spec, traffic  # noqa: E402


def pct(values, q):
    return float(np.percentile(values, q)) if values else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    try:
        device = run.device_info(cell.chips)
    except run.NoChip as e:
        run.log(f"sweep: {e}")
        return 3
    run.use_compile_cache()
    sz = cell.arch.sizes(cell.config)
    eng = run.build_engine(cell, args.seed)
    drv = run.Driver(eng, annotate=False)
    run.warm_up(drv, sz["vocab"])
    results = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = traffic.open_requests(cell.traffic, rate, args.seconds,
                                     args.seed + k, sz["vocab"])
        n0 = len(drv.ticks)
        t0, t_close, tracks = run.open_loop(
            drv, reqs, args.seconds, run.Tracer(False, cell.name, args.seed))
        ticks = [t for t in drv.ticks[n0:] if t["t1"] <= t_close]
        half = t0 + args.seconds / 2
        waits = [((t.req.admit_time or t_close) - t.due) * 1e3
                 for t in tracks]
        first = [w for w, t in zip(waits, tracks) if t.due < half]
        second = [w for w, t in zip(waits, tracks) if t.due >= half]
        ttft = [(t.first - t.due) * 1e3 for t in tracks if t.first]
        tpot = [g for t in tracks for g in t.gaps]
        row = {
            "rate": rate, "due": len(tracks),
            "ttft_ms": [pct(ttft, q) for q in (25, 50, 75, 95)],
            "tpot_ms": [pct(tpot, q) for q in (25, 50, 75, 95)],
            "queue_wait_ms_first_half_p50": pct(first, 50),
            "queue_wait_ms_second_half_p50": pct(second, 50),
            "unadmitted_at_close": sum(
                1 for t in tracks if t.req.admit_time is None
                or t.req.admit_time > t_close),
            "busy_slot_share": (sum(len(t["rows"]) for t in ticks)
                                / max(1, len(ticks)) / eng.slots),
            "ticks": len(ticks),
            "prefill_ticks": sum(t["kind"] == "prefill" for t in ticks),
            "device": device["kind"],
        }
        results.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
