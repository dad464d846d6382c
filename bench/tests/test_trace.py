"""The reduction from a profile to the per-layer metrics' inputs."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def made():
    """Two engine calls and a sleep in a 1-second window: a prefill span
    [0.1, 0.3] whose program runs [0.12, 0.28], its insert [0.30, 0.32]
    after the span, and a decode span [0.4, 0.6] running [0.42, 0.58]."""
    return trace.Trace(
        ops=[("fusion.1", 0.12, 0.20), ("fusion.2", 0.18, 0.28),
             ("copy", 0.31, 0.33), ("while.3", 0.42, 0.58)],
        modules=[("jit__unknown", 0.12, 0.28), ("jit__insert_slot", 0.31, 0.33),
                 ("jit__unknown", 0.42, 0.58)],
        spans=[("prefill", 0, 0.1, 0.3), ("decode", 1, 0.4, 0.6),
               ("sleep", -1, 0.6, 0.95), ("window", -1, 0.0, 1.0)])


def test_union_and_busy():
    tr = made()
    assert trace.union(tr.ops, 0.0, 1.0) == [(0.12, 0.28), (0.31, 0.33),
                                             (0.42, 0.58)]
    assert trace.busy_s(tr, *tr.window) == pytest.approx(0.34)
    assert trace.busy_s(tr, 0.2, 0.5) == pytest.approx(0.08 + 0.02 + 0.08)


def test_programs_match_the_span_that_issued_them():
    tr = made()
    assert trace.program_time(tr, *tr.window) == pytest.approx(
        {0: 0.16, 1: 0.16})


def test_breakdown_names_ops_and_gaps():
    b = trace.breakdown(made(), 0.0, 1.0)
    ops = dict(b["device_ops"])
    assert ops["decode:while.3"] == pytest.approx(0.16)
    assert ops["prefill:fusion.1"] == pytest.approx(0.08)
    assert ops["other:copy"] == pytest.approx(0.02)
    # idle: [0, .12] (midpoint .06: no span), [.28, .31] prefill, [.33,
    # .42] (midpoint .375: no span), [.58, 1] sleep (midpoint .79)
    gaps = b["idle_gaps"]
    assert [g[0] for g in gaps] == ["sleep at 0.580 s", "loop at 0.000 s",
                                    "loop at 0.330 s", "prefill at 0.280 s"]
    assert sum(g[1] for g in gaps) == pytest.approx(1.0 - 0.34)
    assert gaps[0][1] == pytest.approx(0.42)


def test_reads_a_profile_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("decode", id=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    tr = trace.load(str(path))
    kinds = [(s[0], s[1]) for s in tr.spans]
    assert ("decode", 0) in kinds and ("decode", 1) in kinds
    t0, t1 = tr.window
    assert tr.ops and 0 < trace.busy_s(tr, t0, t1) <= t1 - t0
    assert set(trace.program_time(tr, t0, t1)) == {0, 1}


def recorded(shift=0.0):
    """The slice recorded on the chip, with the device's events moved
    ``shift`` seconds later, as the profiler can put them."""
    d = json.loads((DATA / "trace_mixtral_chat_v5e.json").read_text())
    return trace.Trace(
        ops=[(n, a + shift, b + shift) for n, a, b in d["ops"]],
        modules=[(n, a + shift, b + shift) for n, a, b in d["modules"]],
        spans=[tuple(s) for s in d["spans"]])


@pytest.mark.parametrize("shift", [0.0, 0.0317, -0.012])
def test_the_device_clock_offset_is_found_on_a_recorded_slice(shift):
    tr = trace.align(recorded(shift))
    assert tr.offset == pytest.approx(shift, abs=0.002)
    t = trace.program_time(tr, *tr.window)
    # two prefills of the 2048-token bucket and four decode steps
    assert t[251] == pytest.approx(0.2892, abs=2e-4)
    assert t[253] == pytest.approx(0.2892, abs=2e-4)
    assert [round(t[i], 4) for i in (252, 254, 255, 256)] == [0.0313] * 4
    b = trace.breakdown(tr, *tr.window)
    assert b["device_ops"][0][0].startswith("prefill:")
    busy = trace.busy_s(tr, *tr.window)
    assert 0.9 < busy / (tr.window[1] - tr.window[0]) < 1.0


def test_only_planes_with_operations_count_as_chips():
    from types import SimpleNamespace as N
    ev = lambda name, t, d, **st: N(name=name, start_ns=t, duration_ns=d,  # noqa: E731
                                    stats=st)
    planes = [
        N(name="/device:TPU:0", lines=[
            N(name="XLA Ops", events=[ev("%fusion.1 = bf16[8] fusion()",
                                          100, 50)]),
            N(name="XLA Modules", events=[ev("jit__unknown(1)", 90, 70)])]),
        N(name="/device:CUSTOM:Megascale Trace", lines=[]),
        N(name="/host:CPU", lines=[N(name="python3", events=[
            ev("window", 0, 400), ev("decode", 80, 100, id=3)])])]
    tr = trace.from_planes(planes)
    assert tr.devices == 1 and [op[0] for op in tr.ops] == ["fusion.1"]
    assert trace.busy_s(tr, *tr.window) == pytest.approx(50e-9)
    assert trace.program_time(tr, *tr.window) == pytest.approx({3: 70e-9})
