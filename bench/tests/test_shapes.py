"""bench/shapes.py against operations and bytes worked out by hand for one
layer of each configuration."""
import dataclasses

from bench import shapes
from bench.spec import BENCH_DIR, load_json


def one_layer(config):
    m = shapes.dims(load_json(BENCH_DIR / "configs" / f"{config}.json"))
    return dataclasses.replace(m, layers=1)


def test_qwen2_7b_prefill_one_layer():
    m = one_layer("qwen2-7b")
    w = shapes.prefill(m, 1000)
    # q, k, v, o: 3584 * (28 + 2 * 4) * 128 + 28 * 128 * 3584 = 29,360,128
    # SwiGLU: 3 * 3584 * 18944 = 203,685,888
    # 1000 tokens * 2 * (29,360,128 + 203,685,888)    = 466,092,032,000
    # causal attention: 4 * 28 * 128 * 1000 * 1001 / 2 =   7,175,168,000
    # head, the last row only: 2 * 3584 * 152064      =   1,089,994,752
    assert w["flops"] == 474_357_194_752
    # weights (29,360,128 + 203,685,888 + 2 norms * 3584) * 2 B
    # + K and V written: 2 * 1000 * 4 * 128 * 2 B + head 3584 * 152064 * 2 B
    assert w["bytes"] == 466_106_368 + 2_048_000 + 1_089_994_752


def test_mixtral_8x7b_decode_one_layer():
    m = one_layer("mixtral-8x7b")
    w = shapes.decode(m, [100, 300])
    # q, k, v, o: 4096 * (32 + 2 * 8) * 128 + 32 * 128 * 4096 = 41,943,040
    # one expert: 3 * 4096 * 14336 = 176,160,768; top-2 and the router
    # 2 * 176,160,768 + 4096 * 8 = 352,354,304
    # 2 tokens * 2 * (41,943,040 + 352,354,304)       = 1,577,189,376
    # attention over 400 live positions: 4 * 32 * 128 * 400 = 6,553,600
    # head: 2 tokens * 2 * 4096 * 32000                =   524,288,000
    assert w["flops"] == 2_108_030_976
    # experts two tokens reach: 8 * (1 - (6/8)^2) = 3.5
    # weights (41,943,040 + 3.5 * 176,160,768 + 4096 * 8 + 2 * 4096) * 2 B
    # + KV read 2 * 8 * 128 * 2 B * 400 + head 4096 * 32000 * 2 B
    assert w["bytes"] == 1_317_093_376 + 1_638_400 + 262_144_000
