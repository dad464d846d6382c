"""``decode_host_gap_ms``: the device's idle time before each decode step,
read from the named decode program and the device operations."""
from types import SimpleNamespace

import pytest

from bench import spec, trace
from bench.tests.test_trace import recorded

read = spec.metric_reader("decode_host_gap_ms")


def run_of(tr, window=None):
    return SimpleNamespace(trace=tr, trace_window=window or tr.window)


def steps(gaps_ms, name="jit_engine_decode(7)"):
    """Decode steps of 30 ms, each after an idle gap of ``gaps_ms``; each
    step's own operations start with its program."""
    ops, modules, t = [("fusion.0", 0.0, 0.01)], [], 0.01
    for g in gaps_ms:
        a = t + g * 1e-3
        modules.append((name, a, a + 0.03))
        ops += [("while.3", a, a + 0.02), ("fusion.1", a + 0.02, a + 0.03)]
        t = a + 0.03
    return trace.Trace(ops=ops, modules=modules,
                       spans=[("window", -1, 0.0, t + 0.01)])


def test_the_median_gap_leaves_out_a_freeze():
    assert read(run_of(steps([2.0, 2.5, 120.0, 3.0, 2.0]))) == \
        pytest.approx(2.5)


def test_an_operation_still_running_at_the_step_leaves_no_gap():
    tr = steps([2.0, 4.0, 4.0])
    a = tr.modules[1][1]
    tr.ops.append(("copy", a - 0.003, a + 0.001))   # an insert, say
    tr.ops.sort(key=lambda o: o[1])
    assert read(run_of(tr)) == pytest.approx(2.0)   # gaps 2, 0, 4


def test_only_steps_starting_in_the_window_count():
    tr = steps([1.0, 9.0, 9.0])
    assert read(run_of(tr, (0.0, tr.modules[0][2]))) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["jit__unknown(7)", "jit_engine_prefill(3)"])
def test_nothing_to_read_without_a_named_decode_program(name):
    assert read(run_of(steps([2.0, 3.0], name=name))) is None
    assert read(run_of(trace.Trace(spans=[("window", -1, 0.0, 1.0)]))) \
        is None


def test_reads_the_recorded_chip_slice_once_its_decode_program_is_named():
    tr = trace.align(recorded())
    assert read(run_of(tr)) is None                 # the programs unnamed
    decode = "jit__unknown(11379481269446753459)"
    tr.modules = [("jit_engine_decode(1)" if n == decode else n, a, b)
                  for n, a, b in tr.modules]
    # four steps, idle 0.89, 1.09, 2.31 and 2.27 ms after the operation
    # before each (an insert, or the step before)
    assert read(run_of(tr)) == pytest.approx((1.0883 + 2.2656) / 2, abs=1e-3)
