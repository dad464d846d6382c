"""``bench/engine_split.py``: the decode gaps split over the engine's own
spans, on synthetic traces and in a whole traced run of a tiny cell."""
import json
from types import SimpleNamespace

import pytest

from bench import engine_split, trace


def test_engine_annotations_are_read_from_the_host_planes():
    ev = SimpleNamespace
    planes = [
        ev(name="/device:TPU:0", lines=[ev(name="XLA Ops", events=[
            ev(name="engine.decode", start_ns=0, duration_ns=9, stats=[])])]),
        ev(name="/host:CPU", lines=[ev(name="python", events=[
            ev(name="engine.decode.program", start_ns=3_000_000,
               duration_ns=1_000_000, stats=[]),
            ev(name="decode", start_ns=0, duration_ns=5, stats=[]),
            ev(name="engine.decode", start_ns=2_000_000,
               duration_ns=4_000_000, stats=[("batch", 3)])])])]
    assert engine_split.engine_events(planes) == [
        ("decode", {"batch": 3}, 0.002, 0.006),
        ("decode.program", {}, 0.003, 0.004)]


def step_trace():
    """Three decode steps of 10 ms after gaps of 2, 0 and 3 ms, and the
    engine spans over the host's side of each gap."""
    ops, modules, t, events = [("fusion.0", 0.0, 0.01)], [], 0.01, []
    for i, g in enumerate((0.002, 0.0, 0.003)):
        a = t + g
        modules.append(("jit_engine_decode(1)", a, a + 0.01))
        ops.append(("while.1", a, a + 0.01))
        if g:
            # the decode call starts half-way into the gap: a quarter of the
            # gap uploads inputs, a quarter dispatches the program
            events += [("decode", {}, t + g / 2, a + 0.011),
                       ("decode.inputs", {}, t + g / 2, t + 3 * g / 4),
                       ("decode.program", {}, t + 3 * g / 4, a + 0.0105)]
        t = a + 0.01
    tr = trace.Trace(ops=ops, modules=modules,
                     spans=[("window", -1, 0.0, t + 0.01)])
    return tr, sorted(events, key=lambda e: e[2])


def test_the_gaps_are_the_metrics_and_split_by_span():
    tr, events = step_trace()
    steps, gaps = engine_split.decode_gaps(tr, *tr.window)
    assert steps == 3 and len(gaps) == 2           # the zero gap left out
    assert [b - a for a, b in gaps] == pytest.approx([0.002, 0.003])
    split = engine_split.gap_split(gaps, events)
    assert split["outside"] == pytest.approx(1.25)  # ms, the mean of 1, 1.5
    assert split["decode.inputs"] == pytest.approx(0.625)
    assert split["decode.program"] == pytest.approx(0.625)
    assert split["decode.other"] == pytest.approx(0.0, abs=1e-9)
    assert sum(split.values()) == pytest.approx(2.5)
    assert engine_split.gap_split([], events) == {}


def test_the_expert_share_sums_rows_over_prefills_in_the_window():
    events = [("prefill", {"expert_rows": 800, "expert_rows_needed": 60},
               1.0, 1.2),
              ("prefill", {"expert_rows": 400, "expert_rows_needed": 40},
               2.0, 2.1),
              ("prefill", {"expert_rows": 400, "expert_rows_needed": 400},
               9.0, 9.1),
              ("decode", {"expert_rows": 10, "expert_rows_needed": 1},
               2.5, 2.6)]
    assert engine_split.expert_useful_share(events, 0.5, 5.0) == \
        pytest.approx(100 * 100 / 1200)
    assert engine_split.expert_useful_share(events[3:], 0.5, 5.0) is None


@pytest.mark.parametrize("telemetry", [0, 1])
def test_a_traced_run_of_a_tiny_cell(tiny, capsys, telemetry):
    tiny(moe=True, loop="closed", limit=1.0)
    engine_split.main(["--workload", "tiny", "--seed", "3000000019",
                       "--seconds", "2", "--telemetry", str(telemetry)])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] and res["telemetry"] == bool(telemetry)
    assert res["decode_steps"] > 0 and res["host_decode_calls"] > 0
    assert "decode_host_gap_ms" in res["metrics"]
    if telemetry:
        assert res["spans_recorded"] > 0
        assert 0 < res["prefill_expert_useful_share"] <= 100
        if res["gaps_above_zero"]:
            assert sum(res["gap_split_mean_ms"].values()) == \
                pytest.approx(res["gap_mean_ms"])
    else:
        assert res["spans_recorded"] == 0
        assert res["prefill_expert_useful_share"] is None
        assert res["gap_split_mean_ms"].get("decode.inputs", 0.0) == 0.0
