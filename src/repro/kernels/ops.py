"""Jit'd public wrappers for the Pallas kernels.

On TPU ``flash_attention``, ``decode_attention`` and ``grouped_gemm``
compile natively through Mosaic (``tests/test_tpu_compile.py`` compiles
them for a described v5e at qwen2-7b / mixtral-8x7b widths).  On CPU the
same ``pl.pallas_call`` runs with ``interpret=True`` — the kernel body runs
in Python per grid step, which validates correctness against ref.py.  Any
other backend is an error: nothing interprets on an accelerator.
``wkv_chunked`` does not lower for TPU (``cumsum`` has no Mosaic rule) and
runs interpreted on CPU only.

Head dims that are not MXU-lane aligned (kimi's 112) are zero-padded to the
next multiple of 128 here, not inside the kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_gemm as _gg


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither")


def _pad_hd(x: jax.Array, align: int = 128):
    hd = x.shape[-1]
    pad = (-hd) % align
    if pad == 0:
        return x, hd
    cfgpad = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfgpad), hd


def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128):
    qp, hd = _pad_hd(q)
    kp, _ = _pad_hd(k)
    vp, _ = _pad_hd(v)
    # note: padding v's head dim just widens the output; sliced below.
    # scale must use the true head dim:
    out = _fa.flash_attention(qp * (hd ** -0.5) / (qp.shape[-1] ** -0.5),
                              kp, vp, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=_interpret())
    return out[..., :hd]


def decode_attention(q, k, v, lengths, *, bk=256):
    qp, hd = _pad_hd(q)
    kp, _ = _pad_hd(k)
    vp, _ = _pad_hd(v)
    out = _dec.decode_attention(qp * (hd ** -0.5) / (qp.shape[-1] ** -0.5),
                                kp, vp, lengths, bk=bk,
                                interpret=_interpret())
    return out[..., :hd]


def grouped_gemm(x, w, group_sizes, *, bm=128, bn=128, bkk=512):
    return _gg.grouped_gemm(x, w, group_sizes, bm=bm, bn=bn, bkk=bkk,
                            interpret=_interpret())


def wkv_chunked(r, k, v, w, u, *, chunk=16):
    from repro.kernels import wkv_chunk as _wkv
    return _wkv.wkv_chunked(r, k, v, w, u, chunk=chunk,
                            interpret=_interpret())
