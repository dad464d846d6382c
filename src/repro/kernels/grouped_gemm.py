"""GroupedGEMM Pallas kernel for MoE expert compute (megablox-style).

Layout matches models/moe.py's capacity buffers: x (E, C, d_in),
w (E, d_in, d_out), y (E, C, d_out) with per-expert valid row counts
``group_sizes``.  Grid (E, C/bm, d_out/bn, d_in/bk) with an f32 VMEM
accumulator over the contraction dimension.  Tiles whose m-range lies
entirely beyond group_sizes[e] are SKIPPED — imbalanced expert loads cost
only their own tiles, which is precisely the heterogeneous-task behavior
Frontier's GroupedGEMM operator model predicts (wave quantization over
ragged tiles).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import dot_precision


def _gg_kernel(gs_ref, x_ref, w_ref, y_ref, acc_ref, *,
               bm: int, bn: int, bkk: int, nk: int, precision):
    e = pl.program_id(0)
    im = pl.program_id(1)
    ik = pl.program_id(3)

    rows = gs_ref[e]
    live = (im * bm) < rows

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[...]
        w = w_ref[...]
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        mrow = im * bm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        ok = mrow < rows
        y_ref[...] = jnp.where(ok, acc_ref[...], 0.0).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkk", "interpret"))
def grouped_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                 bm: int = 128, bn: int = 128, bkk: int = 512,
                 interpret: bool = False) -> jax.Array:
    """x (E,C,din) @ w (E,din,dout) with per-expert row validity."""
    E, C, din = x.shape
    dout = w.shape[2]
    bm = min(bm, max(C, 8))
    bn = min(bn, max(dout, 128))
    bkk = min(bkk, max(din, 128))
    Cp = math.ceil(C / bm) * bm
    Np = math.ceil(dout / bn) * bn
    Kp = math.ceil(din / bkk) * bkk
    xr = jnp.pad(x, ((0, 0), (0, Cp - C), (0, Kp - din)))
    wr = jnp.pad(w, ((0, 0), (0, Kp - din), (0, Np - dout)))
    gs = group_sizes.astype(jnp.int32)   # scalar prefetch (SMEM)
    nk = Kp // bkk

    kernel = functools.partial(_gg_kernel, bm=bm, bn=bn, bkk=bkk, nk=nk,
                               precision=dot_precision(x.dtype))
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, Cp // bm, Np // bn, nk),
            in_specs=[
                pl.BlockSpec((None, bm, bkk),
                             lambda e, im, jn, ik, gs: (e, im, ik)),
                pl.BlockSpec((None, bkk, bn),
                             lambda e, im, jn, ik, gs: (e, ik, jn)),
            ],
            out_specs=pl.BlockSpec((None, bm, bn),
                                   lambda e, im, jn, ik, gs: (e, im, jn)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Np), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(gs, xr, wr)
    return y[:, :C, :dout]
