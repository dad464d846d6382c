"""The generator: the seed fixes the token ids; every seed gets the same
sizes and arrival times."""
import numpy as np

from bench import traffic
from bench.spec import BENCH_DIR, load_json


def mix(name):
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def test_the_seed_changes_the_tokens_and_nothing_else():
    m = mix("mixtral-8x7b.chat")
    big = 2 ** 31 + 12345
    a = traffic.schedule(m, 32000, big, 20.0)
    b = traffic.schedule(m, 32000, big, 20.0)
    c = traffic.schedule(m, 32000, big + 1, 20.0)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in
               zip(a.requests, b.requests))
    assert [(r.due, len(r.prompt), r.output_len) for r in a.requests] == \
        [(r.due, len(r.prompt), r.output_len) for r in c.requests]
    assert not np.array_equal(a.requests[0].prompt, c.requests[0].prompt)
    gaps = np.diff([r.due for r in a.requests])
    assert abs(np.mean(gaps) * m["rate_rps"] - 1) < 0.1


def test_lengths_respect_the_clip():
    for path in sorted((BENCH_DIR / "traffic").glob("*.json")):
        m = load_json(path)
        s = traffic.schedule(m, 1000, 3, 30.0)
        for r in s.requests:
            assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
            assert 1 <= r.output_len <= m["output"]["max"]
            assert len(r.prompt) + r.output_len <= m["max_total"]
