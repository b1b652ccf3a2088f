"""RWKV6 WKV recurrence Pallas TPU kernel.

The WKV scan is the RWKV hot spot: per (batch, head), state (hd×hd) evolves
as  S_t = diag(w_t)·S_{t-1} + k_t⊗v_t,  y_t = r_t·(S_{t-1} + diag(u)k_t⊗v_t).

TPU adaptation: the state matrix lives in VMEM scratch across time blocks
(grid = (B·H, n_time_blocks), innermost sequential); within a block the
recurrence runs as a fori_loop that reads one row of the (BT, hd) r/k/v/w
tiles and writes one row of y per step, through the refs.
hd = 64 ⇒ the state tile is 16 KB f32; r/k/v/w/y blocks (BT=128, 64, f32)
add 160 KB — comfortably inside VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import call_kernel

f32 = jnp.float32


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_out_ref,
                state_ref, *, bt: int, n_blocks: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(f32)        # (1, hd)

    # rows are read and written through the refs at the loop counter
    # (pl.ds): Mosaic lowers ref slices, not dynamic slices of values
    def step(t, state):
        row = pl.ds(t, 1)
        r = r_ref[0, row, :].astype(f32)             # (1, hd)
        k = k_ref[0, row, :].astype(f32)
        v = v_ref[0, row, :].astype(f32)
        w = w_ref[0, row, :].astype(f32)
        a = k.T * v                                  # (hd, hd) rank-1
        y = r @ (state + u.T * a)                    # (1, hd)
        y_ref[0, row, :] = y.astype(y_ref.dtype)
        return w.T * state + a

    state_ref[...] = jax.lax.fori_loop(0, bt, step, state_ref[...])

    @pl.when(ti == n_blocks - 1)
    def _emit_state():
        st_out_ref[0] = state_ref[...].astype(st_out_ref.dtype)


def wkv6(r, k, v, w, u, *, block_t: int = 128,
         interpret: bool | None = None):
    """r,k,v,w: (B, S, H, hd); u: (H, hd).

    Returns (y (B,S,H,hd), final state (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    bt = min(block_t, S)
    nt = math.ceil(S / bt)
    pt = nt * bt - S

    # the kernel moves one row per step, and Mosaic can only address a
    # single row at an unaligned offset in 32-bit arrays: operands and y
    # are float32 in HBM (the kernel computes in float32 anyway)
    def prep(x, fill=0.0):
        x = x.astype(f32)
        xp = jnp.pad(x, ((0, 0), (0, pt), (0, 0), (0, 0)),
                     constant_values=fill) if pt else x
        return xp.transpose(0, 2, 1, 3).reshape(B * H, nt * bt, hd)

    rh, kh, vh = prep(r), prep(k), prep(v)
    # pad w with ones (decay 1 = identity) so padded steps don't alter state
    wh = prep(w, fill=1.0)
    uh = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)

    kernel = functools.partial(_wkv_kernel, bt=bt, n_blocks=nt)
    y, st = call_kernel(lambda interpret: pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, bt, hd), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, 1, hd), lambda h, t: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, hd), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, hd, hd), lambda h, t: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nt * bt, hd), f32),
            jax.ShapeDtypeStruct((B * H, hd, hd), f32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), f32)],
        interpret=interpret,
    ), rh, kh, vh, wh, uh, interpret=interpret)
    y = y.reshape(B, H, nt * bt, hd)[:, :, :S].transpose(0, 2, 1, 3)
    return y.astype(r.dtype), st.reshape(B, H, hd, hd)
