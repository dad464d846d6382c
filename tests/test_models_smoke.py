"""Per-architecture reduced smoke tests + decode-path consistency.

Every assigned arch instantiates its reduced config and runs one forward /
train step on CPU (shape + finiteness).  For a representative subset
covering every block family we additionally assert PREFILL+DECODE ==
TEACHER-FORCED FORWARD — the strongest correctness property of the cache
path (ring buffers, RoPE positions, recurrent states, cross-attention).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import NO_RULES, build_model, init_tree
from repro.models.common import shape_tree
from repro.serving.engine import _insert_slot

B, S = 2, 16


def _batch(cfg, rng, S=S):
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    if cfg.encoder_layers:
        frames = jnp.asarray(rng.normal(size=(B, S, cfg.frontend_dim)),
                             jnp.float32)
        return {"frames": frames, "tokens": toks, "labels": toks}
    if cfg.frontend == "patch":
        Sp = 4
        emb = jnp.asarray(rng.normal(size=(B, Sp, cfg.d_model)), jnp.float32)
        return {"tokens": toks[:, Sp:], "embeds": emb, "labels": toks}
    return {"tokens": toks, "labels": toks}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, NO_RULES)
    params = init_tree(jax.random.PRNGKey(0), model.pds(), jnp.float32)
    rng = np.random.default_rng(0)
    batch = _batch(cfg, rng)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    # one gradient step: grads finite, shapes preserved
    g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


@pytest.mark.parametrize("arch", [
    "yi-9b",                  # plain GQA global attention
    "qwen3-8b",               # qk_norm
    "gemma2-27b",             # local/global alternation + softcaps + postnorm
    "mixtral-8x7b",           # MoE + sliding window (ring cache)
    "rwkv6-1.6b",             # rwkv recurrence
    "recurrentgemma-2b",      # RG-LRU + conv + local attn (period-3 + tail)
    "seamless-m4t-large-v2",  # enc-dec with cross-attention
    "pixtral-12b",            # patch-embed frontend
])
def test_prefill_decode_matches_teacher_forcing(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, NO_RULES)
    params = init_tree(jax.random.PRNGKey(1), model.pds(), jnp.float32)
    rng = np.random.default_rng(1)
    S_all = 12
    batch = _batch(cfg, rng, S=S_all)
    toks = batch["tokens"]
    S_txt = toks.shape[1]
    k = S_txt - 3   # prefill prefix length (text tokens)

    if cfg.encoder_layers:
        full_logits, _ = jax.jit(
            lambda p, b: model.prefill(p, b, all_logits=True))(
                params, {"frames": batch["frames"], "tokens": toks})
        pre = {"frames": batch["frames"], "tokens": toks[:, :k]}
    elif cfg.frontend == "patch":
        full_logits, _ = jax.jit(
            lambda p, b: model.prefill(p, b, all_logits=True))(
                params, {"tokens": toks, "embeds": batch["embeds"]})
        pre = {"tokens": toks[:, :k], "embeds": batch["embeds"]}
    else:
        full_logits, _ = jax.jit(
            lambda p, b: model.prefill(p, b, all_logits=True))(
                params, {"tokens": toks})
        pre = {"tokens": toks[:, :k]}

    # prefill prefix, then decode the remaining tokens teacher-forced
    patch_off = batch["embeds"].shape[1] if cfg.frontend == "patch" else 0
    _, cache = model.prefill(params, pre, cache_len=S_txt + patch_off)
    decode = jax.jit(model.decode)
    for t in range(k, S_txt):
        pos = jnp.int32(t + patch_off)
        logits, cache = decode(params, cache, toks[:, t:t + 1], pos)
        want = full_logits[:, t + patch_off]
        got = logits[:, 0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-3, rtol=2e-3)


def test_per_row_positions_match_uniform():
    """Vector pos with equal entries must equal scalar pos decode."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg, NO_RULES)
    params = init_tree(jax.random.PRNGKey(2), model.pds(), jnp.float32)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 8)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks}, cache_len=16)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    l1, _ = model.decode(params, cache, nxt, jnp.int32(8))
    l2, _ = model.decode(params, cache, nxt, jnp.full((B,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch, prefix", [
    ("yi-9b", (5, 11, 17)),         # global attention
    ("mixtral-8x7b", (9, 21, 28)),  # window 16: two rows' rings have wrapped
])
def test_staggered_rows_decode_in_place(arch, prefix):
    """Rows at different positions in one batch, as MiniEngine serves them:
    each row's prefix is prefilled alone and put in its slot with
    ``_insert_slot``.  Each decode step matches every row's full-sequence
    logits at its own position, and changes nothing of the cache but one
    K/V row per slot and layer, at ``pos % Sc``."""
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:  # dropless, so prefill and decode route alike
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor_eval=float(cfg.moe.num_experts)))
    model = build_model(cfg, NO_RULES)
    params = init_tree(jax.random.PRNGKey(3), model.pds(), jnp.float32)
    rng = np.random.default_rng(3)
    rows, steps, max_seq = len(prefix), 4, 40
    toks = rng.integers(0, cfg.vocab_size, (rows, max(prefix) + steps))
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        shape_tree(model.cache_pds(rows, max_seq), jnp.float32))
    want = []
    for b, n in enumerate(prefix):
        seq = jnp.asarray(toks[b:b + 1, :n + steps], jnp.int32)
        full, _ = model.prefill(params, {"tokens": seq}, all_logits=True)
        want.append(np.asarray(full[0, n:]))
        _, one = model.prefill(params, {"tokens": seq[:, :n]},
                               cache_len=max_seq)
        cache = _insert_slot(cache, one, b)

    decode = jax.jit(model.decode)
    pos = np.asarray(prefix, np.int32)
    for i in range(steps):
        cur = jnp.asarray(toks[np.arange(rows), pos][:, None], jnp.int32)
        logits, new = decode(params, cache, cur, jnp.asarray(pos))
        for b in range(rows):
            np.testing.assert_allclose(np.asarray(logits[b, 0]), want[b][i],
                                       atol=2e-3, rtol=2e-3)
        for old_kv, new_kv in zip(jax.tree_util.tree_leaves(cache),
                                  jax.tree_util.tree_leaves(new)):
            old_kv, new_kv = np.asarray(old_kv), np.asarray(new_kv)
            hit = np.zeros(old_kv.shape[:3], bool)  # (layers, rows, Sc)
            hit[:, np.arange(rows), pos % old_kv.shape[2]] = True
            np.testing.assert_array_equal(new_kv[~hit], old_kv[~hit])
            assert (new_kv[hit] != old_kv[hit]).any(axis=(-2, -1)).all()
        cache, pos = new, pos + 1


def test_param_counts_match_full_configs():
    """Full-config param counts are in the advertised ballpark."""
    expect = {
        "yi-9b": (8.0e9, 10.5e9),
        "qwen3-8b": (7.5e9, 9.5e9),
        "qwen3-32b": (31e9, 36e9),
        "gemma2-27b": (26e9, 30e9),
        "mixtral-8x7b": (45e9, 49e9),
        "kimi-k2-1t-a32b": (0.95e12, 1.15e12),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e},{hi:.1e}]"
