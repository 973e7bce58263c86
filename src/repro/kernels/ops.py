"""Jitted public wrappers for the Pallas kernels.

Callers choose ``interpret`` explicitly: ``True`` runs the Pallas
interpreter (the CPU path of the tests), ``False`` lowers to Mosaic for
the TPU.  The wrappers adapt the model-layer layouts ([B,T,H,D] GQA
attention, SSD block tensors) to the kernels' flattened layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


@functools.partial(jax.jit, static_argnames=("scale", "causal", "interpret"))
def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
              causal: bool = True, interpret: bool) -> jax.Array:
    """[B,T,Hq,D] x [B,S,Hkv,D] GQA flash attention (kv broadcast to q
    heads, batch*heads flattened for the kernel)."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    kf = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vf = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    kf = kf.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    out = flash_attention(qf, kf, vf, scale=scale, causal=causal,
                          interpret=interpret)
    return out.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
        Cm: jax.Array, *, chunk: int = 256, interpret: bool) -> jax.Array:
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
