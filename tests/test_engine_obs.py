"""MiniEngine's own telemetry: spans and counters in ``repro.obs``, profiler
annotations on the device trace's clock, and named step programs.  CPU,
smoke widths."""
import glob
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe
from repro.obs import Telemetry, read_spans_jsonl, write_chrome_trace
from repro.obs import write_spans_jsonl
from repro.serving import engine as engine_mod
from repro.serving.engine import MiniEngine

SLOTS, MAX_SEQ, NEW = 2, 64, 4
PROMPT_LENS = (8, 20, 5, 40, 12)          # more requests than slots
PHASES = {"prefill": ("prefill.inputs", "prefill.program", "prefill.insert"),
          "decode": ("decode.inputs", "decode.program", "decode.bookkeep")}
# the annotation names bench/trace.py reads as the benchmark's own spans
BENCH_KINDS = ("prefill", "decode", "sleep", "window")


def serve(model: str, telemetry=None):
    cfg = get_config(model, smoke=True)
    eng = MiniEngine(cfg, max_slots=SLOTS, max_seq=MAX_SEQ, seed=3,
                     telemetry=telemetry)
    rng = np.random.default_rng(5)
    reqs = eng.submit([rng.integers(0, cfg.vocab_size, n)
                       for n in PROMPT_LENS], NEW)
    rep = eng.run()
    return eng, reqs, rep


@pytest.fixture(scope="module", params=["qwen2-7b", "mixtral-8x7b"])
def traced(request):
    tel = Telemetry()
    eng, reqs, rep = serve(request.param, tel)
    return request.param, eng, reqs, rep, tel


def kinds(tel, kind):
    return [s for s in tel.spans if s.kind == kind]


def test_tokens_are_the_same_with_telemetry_on_and_off(traced):
    model, _, reqs, _, _ = traced
    _, plain, _ = serve(model)
    assert [r.tokens for r in reqs] == [r.tokens for r in plain]
    assert all(len(r.tokens) == NEW for r in reqs)


def test_each_request_has_one_queue_wait_and_one_prefill(traced):
    _, _, reqs, _, tel = traced
    for kind in ("queue_wait", "prefill"):
        assert sorted(s.rid for s in kinds(tel, kind)) == \
            [r.rid for r in reqs]
    start = {s.rid: s.start for s in kinds(tel, "prefill")}
    for s in kinds(tel, "queue_wait"):
        assert s.end == start[s.rid]
        assert s.start == reqs[s.rid].submitted


def test_children_lie_inside_their_parent_and_name_it(traced):
    _, _, _, _, tel = traced
    children = [s for s in tel.spans if s.parent is not None]
    assert len(children) == sum(len(kinds(tel, k)) for k in
                                ("prefill",) + PHASES["prefill"]
                                + PHASES["decode"])
    for s in children:
        p = tel.spans[s.parent]
        assert p.start <= s.start <= s.end <= p.end
        want = "admit" if s.kind == "prefill" else s.kind.split(".")[0]
        assert p.kind == want
    for call, phases in PHASES.items():
        for c in kinds(tel, call):
            i = tel.spans.index(c)
            got = [s.kind for s in tel.spans if s.parent == i]
            assert got == list(phases)


def test_one_decode_span_per_decode_step(traced):
    _, eng, _, rep, tel = traced
    assert len(kinds(tel, "decode")) == rep["decode_steps"] > 0
    assert len(kinds(tel, "admit")) == rep["decode_steps"]   # one a pass
    assert [s.meta["batch"] for s in kinds(tel, "decode")] == \
        [s["batch"] for s in eng.step_log if s["kind"] == "decode"]


def test_meta_carries_tokens_bucket_batch_and_contexts(traced):
    _, _, reqs, _, tel = traced
    for s in kinds(tel, "prefill"):
        n = len(reqs[s.rid].prompt)
        assert s.meta["tokens"] == n
        assert s.meta["bucket"] == min(engine_mod._bucket(n), MAX_SEQ)
        assert 0 <= s.meta["slot"] < SLOTS
        assert 0 <= s.meta["decoding"] < SLOTS
    first = kinds(tel, "decode")[0]
    # the first step runs the first two requests, each one token past its
    # prompt: live contexts are prompt + 1
    assert first.meta["batch"] == SLOTS
    lens = [PROMPT_LENS[0] + 1, PROMPT_LENS[1] + 1]
    assert first.meta["ctx_sum"] == sum(lens)
    assert first.meta["ctx_max"] == max(lens)
    waiting = tel.counters.series("waiting")
    assert waiting[0][1] == len(PROMPT_LENS) and waiting[-1][1] == 0
    assert len(tel.counters.series("active_slots")) == \
        len(kinds(tel, "admit"))


def test_expert_rows_are_what_the_moe_layer_dispatches(traced):
    _, eng, _, _, tel = traced
    calls = kinds(tel, "prefill") + kinds(tel, "decode")
    if eng.cfg.moe is None:
        assert not any("expert_rows" in s.meta for s in calls)
        assert "expert_rows" not in tel.counters.names()
        return
    m = eng.cfg.moe
    E, k, cf = m.num_experts, m.top_k, m.capacity_factor_eval
    for s in calls:
        T = s.meta["bucket"] if s.kind == "prefill" else SLOTS
        real = s.meta["tokens"] if s.kind == "prefill" else s.meta["batch"]
        assert s.meta["expert_rows"] == E * moe._capacity(T, k, E, cf,
                                                          train=False)
        assert s.meta["expert_rows_needed"] == real * k
    assert len(tel.counters.series("expert_rows")) == len(calls)


def test_compile_counts_are_sampled_per_program_when_they_change(traced):
    _, _, reqs, _, tel = traced
    buckets = {min(engine_mod._bucket(len(r.prompt)), MAX_SEQ) for r in reqs}
    prefill = tel.counters.series("compiled.engine_prefill")
    assert [v for _, v in prefill] == list(range(1, len(buckets) + 1))
    assert sum("compiled" in s.meta for s in kinds(tel, "prefill")) == \
        len(buckets)
    assert [v for _, v in tel.counters.series("compiled.engine_decode")] \
        == [1]
    assert [v for _, v in tel.counters.series("compiled._insert_slot")] \
        == [1]


def test_attached_after_warm_up_it_records_only_what_follows():
    cfg = get_config("qwen2-7b", smoke=True)
    eng = MiniEngine(cfg, max_slots=SLOTS, max_seq=MAX_SEQ, seed=3)
    rng = np.random.default_rng(0)
    eng.submit([rng.integers(0, cfg.vocab_size, 9)], 2)
    eng.run()
    eng.telemetry = tel = Telemetry()
    eng.submit([rng.integers(0, cfg.vocab_size, 10)], 3)
    eng.run()
    assert [s.rid for s in kinds(tel, "prefill")] == [1]
    assert len(kinds(tel, "decode")) == 2
    # the counts seen when the recorder arrived: nothing compiled since
    assert [v for _, v in tel.counters.series("compiled.engine_prefill")] \
        == [1]


def test_without_telemetry_no_recorder_or_profiler_call(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("called with telemetry off")

    for name in ("span", "close_span", "counter"):
        monkeypatch.setattr(Telemetry, name, refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    clock = []
    real = time.perf_counter
    monkeypatch.setattr(engine_mod.time, "perf_counter",
                        lambda: clock.append(1) or real())
    eng, reqs, rep = serve("qwen2-7b")
    assert all(len(r.tokens) == NEW for r in reqs)
    # the clock reads the engine made before it had telemetry: one per
    # submit, three per prefill and per decode step, two per run
    assert len(clock) == 1 + 3 * len(reqs) + 3 * rep["decode_steps"] + 2


@pytest.mark.parametrize("program,closed", [
    ("_prefill_jit", ["prefill.program", "prefill", "admit"]),
    ("_decode_jit", ["decode.program", "decode"])])
def test_a_step_that_raises_leaves_no_span_open(monkeypatch, program,
                                                closed):
    stack, exited = [], []

    class Annotation:
        def __init__(self, name, **stats):
            self.name = name

        def __enter__(self):
            stack.append(self.name)

        def __exit__(self, *exc):
            assert stack.pop() == self.name     # innermost first
            exited.append(self.name)

        def set_metadata(self, **stats):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    cfg = get_config("qwen2-7b", smoke=True)
    tel = Telemetry()
    eng = MiniEngine(cfg, max_slots=SLOTS, max_seq=MAX_SEQ, seed=3,
                     telemetry=tel)
    eng.submit([np.arange(9)], 3)
    eng._admit()

    def fail(*a, **k):
        raise RuntimeError("out of memory")

    setattr(eng, program, fail)
    eng.submit([np.arange(7)], 3)
    with pytest.raises(RuntimeError, match="out of memory"):
        eng._admit()
        eng._decode_step()
    assert stack == [] and eng._open == []
    assert exited[-len(closed):] == [f"engine.{k}" for k in closed]
    ended = {s.kind: s for s in tel.spans}      # the last of each kind
    for k in closed:
        assert ended[k].end > ended[k].start


def test_step_programs_have_stable_names_in_a_cpu_profile(tmp_path):
    from jax.profiler import ProfileData
    cfg = get_config("mixtral-8x7b", smoke=True)
    eng = MiniEngine(cfg, max_slots=SLOTS, max_seq=MAX_SEQ, seed=3)
    rng = np.random.default_rng(2)
    eng.submit([rng.integers(0, cfg.vocab_size, 9)], 2)
    eng.run()
    eng.telemetry = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit([rng.integers(0, cfg.vocab_size, 10)], 3)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    modules, names, stats = set(), set(), {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_module" in st:
                    modules.add(st["hlo_module"])
                else:
                    names.add(ev.name)
                    stats.setdefault(ev.name, st)
    assert {"jit_engine_prefill", "jit_engine_decode",
            "jit__insert_slot"} <= modules
    assert "jit__unknown" not in modules
    engine = {n for n in names if n.startswith("engine.")}
    assert {"engine.admit", "engine.prefill", "engine.decode"} | {
        f"engine.{p}" for ph in PHASES.values() for p in ph} == engine
    assert not names & set(BENCH_KINDS)
    # meta and the call's counters ride on the annotation as stats
    pre = stats["engine.prefill"]
    assert pre["tokens"] == 10 and pre["bucket"] == 16
    assert pre["expert_rows_needed"] == 10 * cfg.moe.top_k
    assert stats["engine.decode"]["batch"] == 1


def test_an_engine_recording_exports_and_reads_back(traced, tmp_path):
    _, _, _, _, tel = traced
    out = tmp_path / "engine.trace.json"
    write_chrome_trace(tel, str(out))
    evs = json.loads(out.read_text())["traceEvents"]
    assert {e["name"] for e in evs if e["ph"] == "X"} >= {
        "admit", "prefill", "decode", "decode.program"}
    assert {e["name"] for e in evs if e["ph"] == "C"} >= {
        "waiting", "active_slots"}
    child = next(e for e in evs if e["name"] == "prefill.insert")
    assert isinstance(child["args"]["parent"], int)
    jl = tmp_path / "engine.spans.jsonl"
    write_spans_jsonl(tel, str(jl))
    back = read_spans_jsonl(str(jl))["spans"]
    assert [(s.kind, s.parent, s.meta) for s in back] == \
        [(s.kind, s.parent, s.meta) for s in tel.spans]
