"""Jitted public wrappers around the Pallas kernels.

On a TPU the calls compile to Mosaic; elsewhere (the CPU test path) the
kernel body runs through the Pallas interpreter, which is how the kernels
are validated against ref.py.  ``repro.kernels.call_kernel`` makes the
choice for the platform each program is lowered for.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rwkv6_wkv import wkv6 as _wkv6


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k)


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, cache_len, *, block_k: int = 512):
    return _decode(q, k_cache, v_cache, cache_len, block_k=block_k)


@partial(jax.jit, static_argnames=("block_t",))
def wkv6(r, k, v, w, u, *, block_t: int = 128):
    return _wkv6(r, k, v, w, u, block_t=block_t)
