"""trace_reduce on a small recorded trace (bench/testdata): busy and idle
time, kernel time, self time and gap attribution repeat exactly; ticks
pair with their spans by order and take their kind from the record."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

EV = json.loads((ROOT / "bench/testdata/trace_small.json").read_text())
LO, HI = EV["window"]


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    # [150, 390] holds its nested ops; [395, 398]; [520, 690]; [990, 1000)
    assert tr.merged(EV["ops"], LO, HI) == [(150, 390), (395, 398),
                                            (520, 690), (990, 1000)]
    assert tr.busy_ns(EV["ops"], LO, HI) == 240 + 3 + 170 + 10
    # one tick's device time: the ops inside its host span
    assert tr.busy_ns(EV["ops"], 100, 400) == 243


def test_kernel_time_by_name_inside_a_tick():
    tick = tr.spans(EV["host"], "bench.tick")[0]
    calls = tr.ops_in(EV["ops"], tick[1], tick[2], "paged_prefill_fwd_pallas")
    assert [d for _, _, d in calls] == [100]
    assert tr.ops_in(EV["ops"], 500, 700, "paged_decode_fwd_pallas")[0][2] \
        == 70
    assert tr.op_kind("%paged_decode_fwd_pallas.4") == \
        "paged_decode_fwd_pallas"


def test_self_times_subtract_nested_ops():
    st = tr.self_times(EV["ops"], LO, HI)
    assert st["while"] == (240 - 100 - 30 - 80) + (170 - 70 - 50)
    assert st["convolution_add_fusion"] == 80
    assert st["paged_prefill_fwd_pallas"] == 100
    assert tr.top_ops(EV["ops"], LO, HI, top=2) == [
        ["paged_prefill_fwd_pallas", 100e-9],
        ["convolution_add_fusion", 80e-9]]  # ties in name order


def _ticks(*kinds):
    return [{"kind": k, "t0": 1.0 + i} for i, k in enumerate(kinds)]


def test_ticks_pair_with_their_spans_by_order():
    ticks = _ticks("prefill", "decode")
    pairs = run.pair_traced_ticks(ticks, EV, host_start=0.5)
    assert pairs == [(ticks[0], (100, 400)), (ticks[1], (500, 700))]
    # a tick before the profiler started has no span
    early = {"kind": "decode", "t0": 0.2}
    assert run.pair_traced_ticks([early] + ticks, EV, 0.5) == pairs
    # counts that disagree give no pairs, so no trace-based metric
    assert run.pair_traced_ticks(ticks[:1], EV, 0.5) == []


def test_idle_gaps_are_named_by_the_innermost_host_span():
    pairs = run.pair_traced_ticks(_ticks("prefill", "decode"), EV, 0.5)
    host = run.tick_kinds_named(EV["host"], pairs)
    assert [h[0] for h in host] == ["bench.traced", "bench.tick.prefill",
                                    "bench.submit", "bench.tick.decode"]
    gaps = tr.idle_gaps(EV["ops"], host, LO, HI, top=4)
    assert gaps == [["host: bench.traced", 300e-9],
                    ["host: bench.traced", 150e-9],
                    ["host: bench.traced", 122e-9],
                    ["host: bench.tick.prefill", 5e-9]]
