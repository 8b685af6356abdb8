"""Jit'd public wrappers for the Pallas kernels.

Off a TPU the kernels execute in interpret mode — the kernel body runs in
Python per grid step, which validates the exact TPU program logic; on a
TPU backend the same calls compile to Mosaic
(:func:`repro.kernels.pallas_compiled` decides).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import pallas_compiled
from repro.kernels import ssd_scan as _ssd


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q, k, v, *, causal=True, window=0, block_q=128, block_k=128, interpret=None
):
    if interpret is None:
        interpret = not pallas_compiled()
    return _fa.flash_attention(
        q, k, v,
        causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk=128, interpret=None):
    if interpret is None:
        interpret = not pallas_compiled()
    return _ssd.ssd_scan(x, dt, a, b_mat, c_mat, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, valid_len, *, block_k=512, interpret=None):
    from repro.kernels import flash_decode as _fd

    if interpret is None:
        interpret = not pallas_compiled()
    return _fd.flash_decode(
        q, k, v, valid_len, block_k=block_k, interpret=interpret
    )
