"""Mixture-of-Experts layer with expert parallelism.

Design (see DESIGN.md):
- The router runs under plain pjit (dense GEMM, auto-sharded).
- Dispatch/expert-compute/combine run inside ``shard_map``:
  tokens are sharded over the batch ("pod","data") axes and *replicated*
  over the "model" axis, so each model shard **locally selects** the tokens
  routed to its expert slice (zero dispatch communication), computes the
  capacity-padded batched expert GEMMs, and the combine is a single
  ``psum`` over "model" — the same all-reduce megatron TP pays for a dense
  FFN.  Token load imbalance therefore shows up as *compute imbalance
  across expert shards*, which is exactly the straggler effect Frontier's
  MoE micro-workflow models.
- Capacity: slots per expert per token-shard C_e = ceil(cf * T_l * k / E)
  (train) or a generous effectively-dropless bound (decode).  Overflowing
  assignments are dropped, GShard-style; the drop fraction is surfaced.

Two weight layouts, one code path:
- EP   (E % tp == 0):  expert axis sharded over "model"; e_offset = rank*E_l.
- TPFF (E  < tp):      experts replicated, expert d_ff sharded over "model"
                       (mixtral's 8 experts on a 16-way axis).

FLOP cost is exactly cf x the ideal expert GEMMs — there is no O(T^2)
one-hot dispatch einsum anywhere.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import PD, AxisRules, activation


def moe_pds(cfg: ModelConfig) -> Dict[str, PD]:
    moe = cfg.moe
    d, ff, E = cfg.d_model, moe.expert_d_ff, moe.num_experts
    p = {
        "router": PD((d, E), ("embed", None), 0.02),
        "w_in": PD((E, d, ff), ("expert", "embed", "mlp")),
        "w_out": PD((E, ff, d), ("expert", "mlp", "embed")),
    }
    if cfg.gated_mlp:
        p["w_gate"] = PD((E, d, ff), ("expert", "embed", "mlp"))
    return p


def _capacity(T_l: int, k: int, E: int, cf: float, *, train: bool) -> int:
    A = T_l * k
    if train:
        return max(1, math.ceil(cf * A / E))
    return min(A, max(16, math.ceil(cf * A / E)))


def _expert_ffn(cfg: ModelConfig, xb, w_in, w_gate, w_out):
    """xb (E_l, C, D) -> (E_l, C, D) via batched expert GEMMs."""
    act = activation(cfg.mlp_act)
    h = jnp.einsum("ecd,edf->ecf", xb, w_in)
    if cfg.gated_mlp:
        h = act(jnp.einsum("ecd,edf->ecf", xb, w_gate)) * h
    else:
        h = act(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _dispatch_compute_combine(cfg: ModelConfig, x_flat, ids, gates,
                              w_in, w_gate, w_out, *,
                              E: int, E_l: int, e_offset, C_e: int):
    """Local (per-shard) capacity dispatch -> expert FFN -> combine.

    x_flat (T_l, D); ids/gates (T_l, k).  Returns (y (T_l, D), kept scalar).
    """
    T_l, D = x_flat.shape
    k = ids.shape[-1]
    A = T_l * k
    flat_ids = ids.reshape(A)
    tok = jnp.arange(A, dtype=jnp.int32) // k

    local = (flat_ids >= e_offset) & (flat_ids < e_offset + E_l)
    le = jnp.where(local, flat_ids - e_offset, E_l).astype(jnp.int32)

    order = jnp.argsort(le, stable=True)          # locals first, by expert
    s_le = le[order]
    s_tok = tok[order]
    s_gate = gates.reshape(A)[order]

    counts = jnp.bincount(le, length=E_l + 1)[:E_l]
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(A, dtype=jnp.int32) - starts[jnp.minimum(s_le, E_l - 1)]
    valid = (s_le < E_l) & (pos < C_e)
    dst = jnp.where(valid, s_le * C_e + pos, E_l * C_e)

    # slot -> source-token map (int scatters are cheap; float traffic below
    # is exactly buffer-sized).
    slot_src = jnp.full((E_l * C_e + 1,), T_l, jnp.int32).at[dst].set(s_tok)[:-1]
    slot_gate = jnp.zeros((E_l * C_e + 1,), gates.dtype).at[dst].set(s_gate)[:-1]

    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, D), x_flat.dtype)], axis=0)
    xb = x_pad[slot_src].reshape(E_l, C_e, D)

    yb = _expert_ffn(cfg, xb, w_in, w_gate, w_out).reshape(E_l * C_e, D)
    yb = yb * slot_gate[:, None].astype(yb.dtype)

    y = jnp.zeros((T_l + 1, D), x_flat.dtype).at[slot_src].add(yb)[:T_l]
    kept = jnp.sum(valid.astype(jnp.float32))
    return y, kept


def _a2a_body(cfg: ModelConfig, xs, idss, gatess, w_in, w_gate, w_out, *,
              E: int, E_l: int, tp: int, C_r: int, C_e: int, mesh):
    """Sequence-sharded EP with all-to-all dispatch (MegaScale-style).

    Tokens enter sharded over BOTH batch ("pod","data") and sequence
    ("model").  Each rank routes its own T_ls tokens into per-destination
    capacity buffers, ships them with one `all_to_all`, computes its local
    experts, and ships results back.  Gates never travel: the return buffer
    is slot-aligned with the send buffer, so weighting happens at the
    source.  Collectives per layer drop from two (B,S,D) all-reduces
    (EP-as-TP combine) to two (B,S,D)*k*cf/tp all-to-alls + one all-gather
    at the sequence-reshard boundary.
    """
    D = xs.shape[-1]
    k = idss.shape[-1]
    x_flat = xs.reshape(-1, D)
    T_ls = x_flat.shape[0]
    A = T_ls * k
    flat_ids = idss.reshape(A)
    tok = jnp.arange(A, dtype=jnp.int32) // k

    # ---- source-side: per-destination-rank capacity buffers ---------------
    dest = (flat_ids // E_l).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True)
    s_dest = dest[order]
    s_tok = tok[order]
    s_gate = gatess.reshape(A)[order]
    s_eid = (flat_ids % E_l)[order].astype(jnp.int32)
    counts = jnp.bincount(dest, length=tp)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(A, dtype=jnp.int32) - starts[s_dest]
    valid = pos < C_r
    dst = jnp.where(valid, s_dest * C_r + pos, tp * C_r)

    slot_src = jnp.full((tp * C_r + 1,), T_ls, jnp.int32).at[dst].set(s_tok)[:-1]
    slot_gate = jnp.zeros((tp * C_r + 1,), gatess.dtype).at[dst].set(s_gate)[:-1]
    slot_eid = jnp.full((tp * C_r + 1,), E_l, jnp.int32).at[dst].set(s_eid)[:-1]

    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, D), x_flat.dtype)], 0)
    xbuf = x_pad[slot_src].reshape(tp, C_r, D)
    eidbuf = slot_eid.reshape(tp, C_r)

    # ---- ship tokens + local-expert ids ------------------------------------
    xr = jax.lax.all_to_all(xbuf, "model", split_axis=0, concat_axis=0,
                            tiled=True)
    eidr = jax.lax.all_to_all(eidbuf, "model", split_axis=0, concat_axis=0,
                              tiled=True)

    # ---- dest-side: per-expert capacity buffers + expert FFN ---------------
    A_r = tp * C_r
    le = eidr.reshape(A_r)
    order2 = jnp.argsort(le, stable=True)
    s_le = le[order2]
    s_slot = jnp.arange(A_r, dtype=jnp.int32)[order2]
    counts2 = jnp.bincount(le, length=E_l + 1)[:E_l]
    starts2 = jnp.concatenate([jnp.zeros((1,), counts2.dtype),
                               jnp.cumsum(counts2)[:-1]])
    pos2 = jnp.arange(A_r, dtype=jnp.int32) - starts2[jnp.minimum(s_le, E_l - 1)]
    valid2 = (s_le < E_l) & (pos2 < C_e)
    dst2 = jnp.where(valid2, s_le * C_e + pos2, E_l * C_e)
    eslot_src = jnp.full((E_l * C_e + 1,), A_r, jnp.int32).at[dst2].set(s_slot)[:-1]

    xr_flat = xr.reshape(A_r, D)
    xr_pad = jnp.concatenate([xr_flat, jnp.zeros((1, D), xr_flat.dtype)], 0)
    xe = xr_pad[eslot_src].reshape(E_l, C_e, D)
    ye = _expert_ffn(cfg, xe, w_in, w_gate, w_out).reshape(E_l * C_e, D)

    yr = jnp.zeros((A_r + 1, D), xs.dtype).at[eslot_src].add(
        ye.astype(xs.dtype))[:A_r]

    # ---- ship back (slot-aligned) and combine at the source ----------------
    ybuf = jax.lax.all_to_all(yr.reshape(tp, C_r, D), "model",
                              split_axis=0, concat_axis=0, tiled=True)
    ybuf = ybuf.reshape(tp * C_r, D) * slot_gate[:, None].astype(xs.dtype)
    y = jnp.zeros((T_ls + 1, D), xs.dtype).at[slot_src].add(ybuf)[:T_ls]

    kept = jax.lax.psum(jnp.sum(valid.astype(jnp.float32)), mesh.axis_names) \
        - jax.lax.psum(jnp.sum((~valid2 & (s_le < E_l)).astype(jnp.float32)),
                       mesh.axis_names)
    return y.reshape(xs.shape), kept


def moe_apply(cfg: ModelConfig, p, x: jax.Array, ax: AxisRules, *,
              train: bool) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,D) -> (y (B,S,D), aux metrics incl. load-balance loss)."""
    moe = cfg.moe
    B, S, D = x.shape
    E, k = moe.num_experts, moe.top_k
    cf = moe.capacity_factor_train if train else moe.capacity_factor_eval

    # ---- router under pjit ------------------------------------------------
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    gates = gates.astype(x.dtype)

    # load-balance aux (switch-style) + router z-loss
    flat_probs = probs.reshape(-1, E)
    count_e = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f_e = count_e / jnp.maximum(count_e.sum(), 1.0)
    P_e = jnp.mean(flat_probs, axis=0)
    lb_loss = E * jnp.sum(jax.lax.stop_gradient(f_e) * P_e)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # ---- dispatch/compute/combine under shard_map -------------------------
    mesh = ax.mesh
    tp = ax.model_size()
    ep_mode = tp > 1 and E % tp == 0

    if mesh is None or mesh.empty or tp <= 1:
        x_flat = x.reshape(B * S, D)
        n_b = 1
        C_e = _capacity(B * S, k, E, cf, train=train)
        y, kept = _dispatch_compute_combine(
            cfg, x_flat, ids.reshape(B * S, k), gates.reshape(B * S, k),
            p["w_in"], p.get("w_gate"), p["w_out"],
            E=E, E_l=E, e_offset=0, C_e=C_e)
        y = y.reshape(B, S, D)
        total = jnp.float32(B * S * k)
    else:
        bspec = ax.batch(B)
        bspec_t = bspec if isinstance(bspec, tuple) else ((bspec,) if bspec else ())
        n_b = 1
        for a in bspec_t:
            n_b *= ax.axis_sizes[a]
        T_l = (B // n_b) * S
        E_l = E // tp if ep_mode else E
        C_e = _capacity(T_l, k, E, cf, train=train)
        xspec = P(bspec, None, None)
        # EP: expert axis sharded.  TPFF: expert d_ff sharded (w_in on its
        # last axis, w_out on its middle axis).
        wspec_in = P("model", None, None) if ep_mode else P(None, None, "model")
        wspec_out = P("model", None, None) if ep_mode else P(None, "model", None)
        a2a_mode = (ep_mode and S % tp == 0
                    and ax.opt("moe_dispatch", "psum") == "a2a")

        def body(xs, idss, gatess, w_in, w_gate, w_out):
            e_off = (jax.lax.axis_index("model") * E_l) if ep_mode else 0
            xf = xs.reshape(-1, D)
            y, kept = _dispatch_compute_combine(
                cfg, xf, idss.reshape(-1, k), gatess.reshape(-1, k),
                w_in, w_gate, w_out, E=E, E_l=E_l, e_offset=e_off, C_e=C_e)
            y = jax.lax.psum(y, "model")
            kept = jax.lax.psum(kept, mesh.axis_names)
            if not ep_mode:  # TPFF ranks duplicate the same assignments
                kept = kept / tp
            return y.reshape(xs.shape), kept

        w_gate = p.get("w_gate")
        if w_gate is None:  # keep arity static for shard_map
            w_gate = jnp.zeros((E, 1, 1), x.dtype)
            gspec = P("model", None, None) if ep_mode else P(None, None, None)
        else:
            gspec = wspec_in
        if a2a_mode:
            import functools as _ft
            T_ls = max(T_l // tp, 1)
            C_r = max(1, math.ceil(cf * T_ls * k / tp))
            xspec_a = P(bspec, "model", None)
            body_a = _ft.partial(_a2a_body, cfg, E=E, E_l=E_l, tp=tp,
                                 C_r=C_r, C_e=C_e, mesh=mesh)
            y, kept = jax.shard_map(
                body_a, mesh=mesh,
                in_specs=(xspec_a, xspec_a, xspec_a, wspec_in, gspec,
                          wspec_out),
                out_specs=(xspec_a, P()),
                check_vma=False,
            )(x, ids, gates, p["w_in"], w_gate, p["w_out"])
        else:
            y, kept = jax.shard_map(
                body, mesh=mesh,
                in_specs=(xspec, xspec, xspec, wspec_in, gspec, wspec_out),
                out_specs=(xspec, P()),
                check_vma=False,
            )(x, ids, gates, p["w_in"], w_gate, p["w_out"])
        total = jnp.float32(B * S * k)
    # rows the expert GEMMs compute for B * S tokens (each batch shard
    # fills E capacity buffers of C_e rows), and the assignments per token
    ax.notes[("moe_expert_rows", B * S)] = (n_b * E * C_e, k)

    aux = {
        "moe_lb_loss": lb_loss,
        "moe_z_loss": z_loss,
        "moe_drop_frac": 1.0 - kept / total,
        "moe_load_cv": jnp.std(count_e) / jnp.maximum(jnp.mean(count_e), 1e-9),
    }
    return y, aux
