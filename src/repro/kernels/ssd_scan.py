"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU-native formulation of the state-space duality algorithm: the grid is
(batch, head-blocks, chunks) with the chunk dimension innermost, so the
[hb, P, N] recurrent state lives in VMEM scratch across the sequential
chunk sweep.  Each grid step does three MXU-friendly matmul groups per
head (intra-chunk C·Bᵀ scores, carried-state readout, chunk-state
update) — the same decomposition the paper uses to turn a scan into
matmuls; the in-chunk cumulative decay is a triangular matmul too.

The kernel works head-major: the wrapper lays ``x``/``dt`` out as
[B, H, T, ...] so every block's two minor dims are (time, feature) and
the head block sits on a major axis, which keeps each block on the
TPU's (8, 128) tiling for any head count (e.g. mamba2-2.7b's 80 heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_dot = functools.partial(jax.lax.dot_general,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, *,
                chunk: int, hb: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    dt = dt_ref[0].astype(jnp.float32)                   # [hb, c]
    dA = dt * a_ref[...].astype(jnp.float32)             # [hb, c] (negative)
    Bm = b_ref[0].astype(jnp.float32)                    # [c, N]
    Cm = c_ref[0].astype(jnp.float32)                    # [c, N]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = col <= row
    # in-chunk cumulative decay in both orientations, as triangular
    # matmuls: cum[h, i] = cumT[i, h] = sum_{j <= i} dA[h, j]
    cum = _dot(dA, (row <= col).astype(jnp.float32), _NN)   # [hb, c]
    cumT = _dot(causal.astype(jnp.float32), dA, _NT)        # [c, hb]
    # dt transposed, as an (exact) identity matmul
    dtT = _dot((row == col).astype(jnp.float32), dt, _NT)   # [c, hb]
    scores = _dot(Cm, Bm, _NT)                              # [c, c]
    # the chunk-end decay per state row, end_p[p, h] = cum[h, c-1]: a
    # matmul, since Mosaic cannot broadcast a [1, 1] value to a tile
    P = x_ref.shape[-1]
    last = (jax.lax.broadcasted_iota(jnp.int32, (P, chunk), 1)
            == chunk - 1).astype(jnp.float32)
    end_p = _dot(last, cumT, _NN)                           # [P, hb]
    for h in range(hb):
        x = x_ref[0, h].astype(jnp.float32)                 # [c, P]
        cum_col = cumT[:, h:h + 1]                          # [c, 1]
        cum_end = cumT[chunk - 1:chunk, h:h + 1]            # [1, 1]
        # intra-chunk: (C B^T * L * dt) @ x, L the masked decay kernel
        seg = cum_col - cum[h:h + 1, :]                     # [c, c]
        decay = jnp.exp(jnp.where(causal, seg, -1e30))
        y_diag = _dot(scores * decay * dt[h:h + 1, :], x, _NN)
        # carried-state readout: y_off[c, p] = e^{cum_c} sum_n C[c,n] st[p,n]
        state = st_ref[h]                                   # [P, N]
        y_off = _dot(Cm, state, _NT) * jnp.exp(cum_col)
        y_ref[0, h] = (y_diag + y_off).astype(y_ref.dtype)
        # chunk-state update
        coef = jnp.exp(cum_end - cum_col) * dtT[:, h:h + 1]  # [c, 1]
        st_ref[h] = state * jnp.exp(end_p[:, h:h + 1]) \
            + _dot(x, Bm * coef, _TN)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256, head_block: int = 8,
             interpret: bool = False) -> jax.Array:
    """x: [B,T,H,P], dt: [B,T,H], A: [H], Bm/Cm: [B,T,N] -> y [B,T,H,P].

    T must be a chunk multiple (pad upstream).  The head block is the
    largest divisor of H up to ``head_block``."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    hb = min(head_block, H)
    while H % hb:
        hb -= 1
    grid = (B, H // hb, T // chunk)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, hb=hb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((hb, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, chunk, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A.reshape(H, 1),
      Bm, Cm)
    return y.transpose(0, 2, 1, 3)
