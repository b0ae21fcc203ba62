"""Chunked RWKV6 WKV scan as a Pallas TPU kernel.

The RWKV6 recurrence

    y_t = r_t . (S_{t-1} + u * k_t (x) v_t)
    S_t = diag(w_t) S_{t-1} + k_t (x) v_t

is sequential per token on GPU (CUDA kernels walk t one by one).  The
TPU-native adaptation processes the sequence in CHUNKS of c tokens:

  inter-chunk   y_state = (r * Wexc) @ S_in                (MXU, c x N @ N x N)
  intra-chunk   A[t,i]  = sum_n r[t,n] k[i,n] e^{cum[t-1,n]-cum[i,n]}  (i<t)
                A[t,t]  = sum_n r[t,n] u[n] k[t,n]
                y_intra = A @ v                             (MXU, c x c @ c x N)
  state update  S_out   = diag(Wall) S_in + (k * Wrem)^T @ v

where cum is the cumulative log-decay inside the chunk.  All decay ratios
are of the form exp(negative), so the computation is numerically stable
without the secondary chunking CUDA implementations need for their
division-based formulation.  The A tensor is built via an explicit
(c, c, N) broadcast — VPU work bounded by c * c * N * 4 bytes of VMEM
(1 MiB at c=64, N=64).

Grid: (B, H, T/c) with the chunk dimension sequential; S rides in VMEM
scratch between chunks.  Validated in interpret mode against
:func:`repro.kernels.ref.rwkv6_ref`.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_scan"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_ref, *,
            chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    r = r_ref[0, :, 0, :].astype(jnp.float32)     # (c, N)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)              # (N,)
    S = s_ref[...]                                # (N, N) [k-dim, v-dim]
    c, N = r.shape

    logw = jnp.log(jnp.maximum(w, 1e-30))         # (c, N) negative
    cum = jnp.cumsum(logw, axis=0)                # inclusive cumulative decay
    cum_exc = cum - logw                          # exclusive (prod_{j<t})

    # inter-chunk: queries see the carried state decayed by cum_exc
    r_dec = r * jnp.exp(cum_exc)                  # (c, N)
    y = jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c, N)

    # intra-chunk pairwise decay: exp(cum_exc[t] - cum[i]) for i < t (<= 1)
    # built as an explicit (c, c, N) tensor — stable, VPU-bound.
    ratio = jnp.exp(
        jnp.clip(cum_exc[:, None, :] - cum[None, :, :], max=0.0)
    )                                             # (c, c, N)
    pair = (r[:, None, :] * k[None, :, :] * ratio).sum(-1)       # (c, c)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(i_idx < t_idx, pair, 0.0)
    diag = (r * u[None, :] * k).sum(-1)           # (c,)
    A = A + jnp.where(i_idx == t_idx, diag[:, None], 0.0)
    y = y + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: S' = diag(prod w) S + (k * remaining-decay)^T @ v
    total = cum[-1]                               # (N,)
    k_rem = k * jnp.exp(total[None, :] - cum)     # (c, N), factors <= 1
    S_new = jnp.exp(total)[:, None] * S + jax.lax.dot_general(
        k_rem, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    s_ref[...] = S_new
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ci == chunks - 1)
    def _finish():
        sT_ref[0, 0] = S_new


def rwkv6_scan(
    r: jnp.ndarray,                # (B, T, H, N)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,                # per-channel decay in (0, 1)
    u: jnp.ndarray,                # (H, N)
    S0: jnp.ndarray,               # (B, H, N, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,T,H,N), S_T (B,H,N,N) float32)."""
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    if T % chunk:
        raise ValueError(f"T={T} must be divisible by chunk={chunk}")
    chunks = T // chunk

    kernel = functools.partial(_kernel, chunks=chunks)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, H, chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, N), lambda b, h, ci: (h, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u, S0)
    return y, sT


def rwkv6_scan_trainable(r, k, v, w, u, S0, *, chunk: int = 64,
                         interpret: bool = False):
    """Chunked Pallas forward with an oracle (sequential-scan) backward —
    trainable today; a chunked backward kernel is the production follow-up."""
    from .ref import rwkv6_ref

    @jax.custom_vjp
    def mix(r, k, v, w, u, S0):
        return rwkv6_scan(r, k, v, w, u, S0, chunk=chunk, interpret=interpret)

    def fwd(r, k, v, w, u, S0):
        return mix(r, k, v, w, u, S0), (r, k, v, w, u, S0)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda *a: rwkv6_ref(*a), *res)
        return vjp(g)

    mix.defvjp(fwd, bwd)
    return mix(r, k, v, w, u, S0)
