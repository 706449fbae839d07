"""bench/phases.py on a small recorded trace with the engine's spans
(bench/testdata/trace_program.json, trace_small.json with ``serve.*``
spans added): the device's wait inside each ``serve.tick``, the time
under each phase, and idle gaps named by the innermost program span, all
against hand-computed values; and every per-layer reader of the
benchmark reads the same with the program's spans beside the trace."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import costs, phases, run, spec  # noqa: E402

DATA = ROOT / "bench/testdata"
SMALL = json.loads((DATA / "trace_small.json").read_text())
PROG = json.loads((DATA / "trace_program.json").read_text())


def test_fixture_is_the_small_trace_with_program_spans():
    for key in ("window", "host", "ops", "devices"):
        assert PROG[key] == SMALL[key]
    assert all(n.startswith("serve.") for n, _, _ in PROG["program"])


def test_host_exposed_is_the_idle_time_inside_each_serve_tick():
    got = phases.host_exposed(PROG)
    # busy [150, 390) [395, 398) [520, 690) [990, 1000); the ticks are
    # [105, 398), idle 45 + 5, and [502, 698), idle 18 + 8
    assert got["ticks"] == 2
    assert got["host_exposed_ms"] == pytest.approx((50 + 26) / 2 / 1e6)
    assert got["by_kind"] == {
        "prefill": {"ticks": 1, "host_exposed_ms": pytest.approx(50e-6)},
        "decode": {"ticks": 1, "host_exposed_ms": pytest.approx(26e-6)}}
    # the window's idle time is 1000 - 423; 501 ns of it lies outside ticks
    assert got["window_idle_s"] == pytest.approx(577e-9)
    assert got["idle_outside_ticks_s"] == pytest.approx(501e-9)
    assert got["sum_s"] == pytest.approx(577e-9)
    assert got["agrees"]
    # without the program's spans there is nothing to read
    assert phases.host_exposed(SMALL) is None


def test_phase_self_time_and_the_idle_time_under_it():
    table = {n: (s * 1e9, i * 1e9) for n, s, i in phases.phase_table(PROG)}
    want = {
        "serve.admit": (14, 14), "serve.reserve": (14, 14),
        "serve.inputs": (20, 20), "serve.dispatch.prefill": (10, 10),
        "serve.dispatch.decode": (5, 5), "serve.sample": (20, 0),
        "serve.wait": (392, 2), "serve.guard": (7, 7),
        "serve.bookkeep": (7, 4), "serve.submit": (7, 7),
        # serve.tick has no self time: its phases cover it. bench.tick
        # outside serve.tick: [100, 105), [398, 400), [500, 502),
        # [698, 700); bench.submit less serve.submit
        "bench.tick": (11, 11), "bench.submit": (3, 3),
        "bench.traced": (490, 480)}
    assert table.keys() == want.keys()
    for name, (self_ns, idle_ns) in want.items():
        assert table[name] == (pytest.approx(self_ns), pytest.approx(idle_ns))
    assert sum(s for s, _ in table.values()) == pytest.approx(1000)
    assert sum(i for _, i in table.values()) == pytest.approx(577)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    assert phases.idle_gaps(PROG, top=4) == [
        ["host: bench.traced", 300e-9], ["host: bench.traced", 150e-9],
        ["host: bench.traced", 122e-9], ["host: serve.guard", 5e-9]]
    assert phases.ticks_holding_one_serve_tick(PROG) == (2, 2)
    assert phases.ticks_holding_one_serve_tick(SMALL) == (0, 2)


def test_innermost_cuts_a_child_at_its_parents_end():
    assert phases.innermost([("a", 0, 10), ("b", 5, 20)], 0, 30) == [
        ("a", 0, 5), ("b", 5, 10), (None, 10, 30)]


def _run_data(trace):
    cell = spec.load_cell("qwen2-0.5b.chat_open")
    ticks = [{"kind": "prefill", "t0": 1.0, "t1": 1.3, "tokens": 1,
              "rows": [(0, 128, False), (300, 1, True)]},
             {"kind": "decode", "t0": 2.0, "t1": 2.2, "tokens": 2,
              "rows": [(128, 1, True), (301, 1, True)]}]
    tracks = [SimpleNamespace(req=SimpleNamespace(admit_time=1.25), due=1.0)]
    return run.RunData(
        cell=cell, sz=cell.arch.sizes(cell.config),
        peak=costs.peaks("TPU v5 lite"), slots=64, ticks=ticks,
        tracks=tracks, trace=trace,
        traced_ticks=run.pair_traced_ticks(ticks, trace, host_start=0.5))


def test_every_layer_reader_reads_the_same_beside_program_spans():
    readers = sorted(p.stem for p in (ROOT / "bench/layer_metrics")
                     .glob("*.py"))
    assert readers
    cell = spec.load_cell("qwen2-0.5b.chat_open")
    plain, with_program = _run_data(SMALL), _run_data(PROG)
    assert len(plain.traced_ticks) == 2
    for name in readers:
        read = cell.layer_reader(name)
        value = read(plain)
        assert value is not None, name
        assert read(with_program) == value, name
