"""Whole runs of a tiny cell on the CPU: a sound run is correct, the traced
run reads the per-layer metrics, and a run whose timed path is broken
underneath, or the float8 control in the program's place, is not correct.
"""
import json

import pytest

from bench import correct, run

SEEDS = (11, 2 ** 31 + 5, 4_000_000_007)
# (moe, number compared, limit).  On eight seeds the tiny dense model's
# served tokens lie at most 0.0073 below the reference's best logit; the
# tokens float8 puts first lie 0.056-0.105 below it, and a fault's further
# still.  The tiny mixture of experts reads a mean gap of 0.0004-0.0041 on
# six seeds, its control 0.0146-0.0185, a fault 0.126 or more; its widest
# gaps overlap (0.036-0.33 against 0.30-0.61).
KINDS = {"dense": (False, "max_logit_gap", 0.02),
         "moe": (True, "mean_logit_gap", 0.008)}


def test_without_a_chip_it_exits_2_and_prints_nothing(capsys):
    assert run.main(["--workload", "mixtral-8x7b.chat", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_run_is_correct_and_the_control_is_not(tiny, seed, kind):
    moe, number, limit = KINDS[kind]
    cell = tiny(moe=moe, loop="open", limit=limit, number=number)
    out, reading = run.run_cell("tiny", seed, 2.0, False, control=True)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and list(out["checks"]) == [number]
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert out["attempted"] > 10 and out["failed"] == 0
    assert reading.tokens >= 150
    control = correct.verdict(reading.control, cell.traffic["check"])
    assert control[number]["value"] > limit
    json.dumps(out)


def test_a_traced_closed_loop_reads_the_layers(tiny):
    cell = tiny(moe=True, loop="closed", limit=1.0)
    out, _ = run.run_cell("tiny", 7, 2.0, True)
    assert out["correct"]
    assert set(out["metrics"]) <= {m.name for m in cell.per_layer}
    assert {"decode_step_ms", "decode_mfu", "prefill_stall_share"} <= \
        set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def token_altered(decode_step):
    def step(model, params, cache, tokens, pos):
        nxt, cache = decode_step(model, params, cache, tokens, pos)
        return (nxt + 1) % model.cfg.vocab_size, cache
    return step


def state_unchanged(decode_step):
    def step(model, params, cache, tokens, pos):
        nxt, _ = decode_step(model, params, cache, tokens, pos)
        return nxt, cache           # the new token's K and V never written
    return step


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("fault", [token_altered, state_unchanged])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault, kind):
    from repro.serving import engine
    monkeypatch.setattr(engine, "decode_step", fault(engine.decode_step))
    moe, number, limit = KINDS[kind]
    tiny(moe=moe, loop="open", limit=limit, number=number)
    out, _ = run.run_cell("tiny", 11, 2.0, False)
    assert not out["correct"], out["checks"]
