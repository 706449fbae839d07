"""Where the chip waits on the serving engine's host work: one traced run
of a cell, read with the engine's own spans beside the benchmark's.

    python bench/phases.py --workload <cell> --seed <n> --seconds <s>

``ServeEngine`` enters a ``jax.profiler.TraceAnnotation`` for each phase
of a tick (``serve.tick`` around ``serve.admit``, ``serve.reserve``,
``serve.inputs``, ``serve.dispatch.<kind>``, ``serve.sample``,
``serve.wait``, ``serve.guard``, ``serve.bookkeep``) and for
``serve.submit``. They share the device trace's clock. This runs
``bench/run.py``'s traced path (``run_cell(..., trace=True)``) once,
keeps those ``serve.*`` spans from the same profiler trace (the
harness's ``trace_reduce.extract`` keeps only ``bench.*`` ones, and what
it returns to the harness is left as it was), and logs to stderr:

  * one line per span name: its self time in the traced window (its
    length less the spans nested in it) and the device idle time under
    that self time;
  * ``host_exposed_ms``: the mean, over the ``serve.tick`` spans in the
    window, of the time inside the span in which no op ran on the chip,
    and the check that it adds up, with the idle time outside them, to
    the window's idle time;
  * the ten longest idle gaps, each named by the innermost span, engine
    or benchmark, over its midpoint.

The last line on stdout is one JSON object with all of it, the run's
per-layer metrics and its ``correct``.
"""
from __future__ import annotations

import argparse
import bisect
import itertools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec, trace_reduce  # noqa: E402

PREFIX = "serve."
CHECK_TOLERANCE = 0.02  # host_exposed x ticks + idle outside vs idle


def program_spans(xplane_path: str) -> list:
    """[[name, start_ns, dur_ns], ...] per ``serve.*`` host span, sorted
    by start. A span's arguments (``serve.tick#step=3,...#``) are cut
    from its name."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name.split("#", 1)[0], e.start_ns,
                            e.duration_ns] for e in line.events
                           if e.name.startswith(PREFIX))
    out.sort(key=lambda h: h[1])
    return out


class Extract:
    """``trace_reduce.extract`` that also returns the program's spans under
    ``"program"`` and keeps the last reduced trace it gave."""

    def __init__(self, harness_extract):
        self.harness_extract = harness_extract
        self.ev = None

    def __call__(self, xplane_path: str) -> dict:
        ev = self.harness_extract(xplane_path)
        ev["program"] = program_spans(xplane_path)
        self.ev = ev
        return ev


class Cover:
    """Sorted disjoint intervals; ``within(a, b)`` is their length inside
    [a, b)."""

    def __init__(self, intervals):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.cum = [0.0, *itertools.accumulate(b - a for a, b in intervals)]

    def _before(self, t):
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(self.ends[i - 1], t) - self.starts[i - 1]

    def within(self, a, b):
        return self._before(b) - self._before(a)


def innermost(spans, lo: float, hi: float) -> list:
    """[(name or None, a, b)]: [lo, hi) cut where spans start and end, each
    piece under the innermost of ``spans`` (name, start, end) covering it.
    Spans nest (one host thread); a child that outlasts its parent is cut
    at the parent's end."""
    out, stack, t = [], [], lo

    def upto(end):
        nonlocal t
        if end > t:
            out.append((stack[-1][0] if stack else None, t, end))
            t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((name, min(e, stack[-1][1]) if stack else e))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return out


def _host_spans(ev):
    return [(n, s, s + d) for n, s, d in ev["host"] + ev.get("program", [])]


def phase_table(ev) -> list:
    """[[name, self_s, idle_s], ...] per span name over the traced window,
    largest idle first; ``None`` is time under no span."""
    lo, hi = ev["window"]
    busy = Cover(trace_reduce.merged(ev["ops"], lo, hi))
    acc: dict = {}
    for name, a, b in innermost(_host_spans(ev), lo, hi):
        row = acc.setdefault(name, [0.0, 0.0])
        row[0] += b - a
        row[1] += (b - a) - busy.within(a, b)
    return sorted(([n, s / 1e9, i / 1e9] for n, (s, i) in acc.items()),
                  key=lambda r: (-r[2], str(r[0])))


def host_exposed(ev) -> dict | None:
    """The device's wait on the engine's host work: the mean, over the
    ``serve.tick`` spans wholly inside the window, of the time inside the
    span with no op on the chip (ms), by tick kind too; and the two sides
    of the check that it adds up to the window's idle time."""
    lo, hi = ev["window"]
    ticks = [(s, e) for _, s, e in trace_reduce.spans(
        ev.get("program", []), "serve.tick") if s >= lo and e <= hi]
    if not ticks:
        return None
    busy = Cover(trace_reduce.merged(ev["ops"], lo, hi))
    dispatch = trace_reduce.spans(ev["program"], "serve.dispatch.")
    inside, kinds = [], {}
    for a, b in ticks:
        idle = (b - a) - busy.within(a, b)
        inside.append(idle)
        kind = next((n.rsplit(".", 1)[1] for n, s, e in dispatch
                     if a <= s and e <= b), "none")
        kinds.setdefault(kind, []).append(idle)
    window_idle = (hi - lo) - trace_reduce.busy_ns(ev["ops"], lo, hi)
    # idle time neither under an op nor inside a counted tick
    covered = sorted(ev["ops"] + [["serve.tick", a, b - a]
                                  for a, b in ticks], key=lambda o: o[1])
    outside = (hi - lo) - trace_reduce.busy_ns(covered, lo, hi)
    total = sum(inside) + outside
    return {
        "host_exposed_ms": sum(inside) / len(ticks) / 1e6,
        "ticks": len(ticks),
        "by_kind": {k: {"ticks": len(v), "host_exposed_ms":
                        sum(v) / len(v) / 1e6} for k, v in kinds.items()},
        "idle_outside_ticks_s": outside / 1e9,
        "window_idle_s": window_idle / 1e9,
        "sum_s": total / 1e9,
        "agrees": abs(total - window_idle) <= CHECK_TOLERANCE * window_idle,
    }


def idle_gaps(ev, top: int = 10) -> list:
    """The ``top`` longest stretches of the window with no op on the chip,
    each named by the innermost span (``serve.*`` or ``bench.*``) over its
    midpoint."""
    lo, hi = ev["window"]
    busy = trace_reduce.merged(ev["ops"], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    pieces = innermost(_host_spans(ev), lo, hi)
    starts = [a for _, a, _ in pieces]
    out = []
    for a, b in gaps[:top]:
        name = pieces[bisect.bisect_right(starts, (a + b) / 2) - 1][0]
        out.append([f"host: {name or 'outside bench spans'}", (b - a) / 1e9])
    return out


def ticks_holding_one_serve_tick(ev) -> tuple[int, int]:
    """(bench.tick spans in the window holding exactly one serve.tick,
    bench.tick spans in the window)."""
    lo, hi = ev["window"]
    inner = trace_reduce.spans(ev.get("program", []), "serve.tick")
    outer = [(s, e) for _, s, e in trace_reduce.spans(ev["host"],
                                                      "bench.tick")
             if s >= lo and e <= hi]
    one = sum(1 for a, b in outer
              if sum(1 for _, s, e in inner if a <= s and e <= b) == 1)
    return one, len(outer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.use_compile_cache()
    extract = Extract(trace_reduce.extract)
    trace_reduce.extract = extract
    try:
        res = run.run_cell(cell, args.seed, args.seconds, True)
    except run.NoChip as e:
        run.log(f"phases: {e}")
        return 3
    finally:
        trace_reduce.extract = extract.harness_extract
    ev = extract.ev
    if ev is None:
        run.log("phases: the run took no trace")
        return 1
    phases = phase_table(ev)
    for name, self_s, idle_s in phases:
        run.log(f"phase {name}: self {self_s * 1e3:.3f} ms, device idle "
                f"under it {idle_s * 1e3:.3f} ms")
    exposed = host_exposed(ev)
    if exposed is not None:
        run.log(f"host_exposed_ms {exposed['host_exposed_ms']:.3f} over "
                f"{exposed['ticks']} ticks {exposed['by_kind']}; check: "
                f"mean x ticks + idle outside ticks = "
                f"{exposed['sum_s']:.6f} s against device idle x window = "
                f"{exposed['window_idle_s']:.6f} s, within "
                f"{CHECK_TOLERANCE:.0%}: {exposed['agrees']}")
    one, n = ticks_holding_one_serve_tick(ev)
    run.log(f"bench.tick spans holding exactly one serve.tick: {one} of {n}")
    gaps = idle_gaps(ev)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "correct": res["correct"],
        "device": res["device"], "metrics": res["metrics"],
        "breakdown": res.get("breakdown"), "host_exposed": exposed,
        "phases": phases, "idle_gaps": gaps,
        "bench_ticks_with_one_serve_tick": [one, n]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
