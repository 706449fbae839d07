"""From a ``jax.profiler`` trace to device busy time, kernel time and the
idle gaps, each on the host's own spans.

``extract`` reads the ``.xplane.pb`` the profiler writes into plain lists
(the shape of ``bench/testdata``): the device's op intervals (the ``XLA
Ops`` line of each ``/device:TPU:*`` plane) and the benchmark's own host
spans (``bench.*`` TraceAnnotations). Device and host events share the
profiler's clock, so a device op can be placed inside the host span that
launched it. Everything after ``extract`` works on those lists only.

Ops nest (a ``while`` holds its body's ops), so busy time is the union of
op intervals, and an op's time in the breakdown is its self time.
"""
from __future__ import annotations

import re
from collections import defaultdict

_SUFFIX = re.compile(r"(\.\d+)+$")


def extract(xplane_path: str) -> dict:
    """{"ops": [[name, start_ns, dur_ns], ...] per device op,
    "host": [[name, start_ns, dur_ns], ...] per ``bench.*`` span,
    "devices": number of TPU planes}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    ops, host, devices = [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.name.split(" = ")[0], e.start_ns,
                                e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    ops.sort(key=lambda o: o[1])
    host.sort(key=lambda h: h[1])
    return {"ops": ops, "host": host, "devices": devices}


def op_kind(name: str) -> str:
    """``%paged_decode_fwd_pallas.4`` -> ``paged_decode_fwd_pallas``."""
    return _SUFFIX.sub("", name.lstrip("%"))


def merged(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the op intervals, clipped to [lo, hi)."""
    out: list[list[float]] = []
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(ops, lo, hi))


def self_times(ops, lo: float, hi: float) -> dict:
    """Self time (ns) of each op kind within [lo, hi): an op's duration
    less the ops nested inside it."""
    acc: dict = defaultdict(float)
    stack: list[list] = []  # [kind, end, child_ns, dur]

    def close(item):
        acc[item[0]] += item[3] - item[2]
        if stack:
            stack[-1][2] += item[3]

    for name, s, d in ops:
        if s + d <= lo or s >= hi:
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        stack.append([op_kind(name), s + d, 0.0, d])
    while stack:
        close(stack.pop())
    return dict(acc)


def spans(host, prefix: str) -> list[tuple[str, float, float]]:
    """Host spans whose name starts with ``prefix``, as (name, start, end)."""
    return [(n, s, s + d) for n, s, d in host if n.startswith(prefix)]


def ops_in(ops, start: float, end: float, kind: str | None = None):
    """Ops that lie wholly inside [start, end], optionally of one kind."""
    return [o for o in ops if o[1] >= start and o[1] + o[2] <= end
            and (kind is None or kind in op_kind(o[0]))]


def idle_gaps(ops, host, lo: float, hi: float, top: int = 10):
    """The ``top`` longest stretches of [lo, hi) with no device op, each
    named by the innermost benchmark host span covering its midpoint
    (``host: outside bench spans`` where none does)."""
    busy = merged(ops, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        inside = [(e - s, n) for n, s, e in spans(host, "bench.")
                  if s <= mid <= e]
        label = min(inside)[1] if inside else "outside bench spans"
        out.append([f"host: {label}", (b - a) / 1e9])
    return out


def top_ops(ops, lo: float, hi: float, top: int = 10):
    """The ``top`` op kinds by self time within [lo, hi), in seconds."""
    st = sorted(self_times(ops, lo, hi).items(),
                key=lambda kv: (-kv[1], kv[0]))
    return [[k, v / 1e9] for k, v in st[:top]]
