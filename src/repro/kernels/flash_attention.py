"""Flash attention (prefill/train forward) as a Pallas TPU kernel.

TPU-native design (vs. the CUDA flash-attention formulation):
  * the grid is (batch, kv_head, q_blocks, kv_blocks) with the kv_blocks
    dimension marked "arbitrary" (sequential) so the online-softmax state
    (m, l, acc) lives in VMEM scratch across kv steps — no atomics, no
    shared-memory tiling; the MXU sees (block_q x D) @ (D x block_k) tiles;
  * block sizes default to 128 — the MXU systolic dimension — and the
    grouped (GQA) q heads for one kv head ride in the same block so K/V
    tiles are loaded once per q block, not once per q head;
  * masking (causal and/or local window) is computed from block-relative
    iotas; fully-masked tiles short-circuit via jnp.where (a production
    kernel would prune them from the grid — block-sparse grids are an
    orthogonal optimisation).

Validated in interpret mode against :func:`repro.kernels.ref.attention_ref`
over shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, :, 0]               # (block_q, g, D)
    bq, g, D = q.shape
    k = k_ref[0, :, 0, :]            # (block_k, D)
    v = v_ref[0, :, 0, :]            # (block_k, D)

    qf = q.reshape(bq * g, D)
    s = jax.lax.dot_general(
        qf.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                         # (bq*g, block_k)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, g), 0)
    q_pos = q_pos.reshape(bq * g, 1)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]               # (bq*g,)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=1)

    acc = acc_ref[...] * alpha[:, None]
    acc += jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, :, 0] = (acc_ref[...] / denom).reshape(bq, g, D).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,                   # (B, S, Hq, D)
    k: jnp.ndarray,                   # (B, S, Hk, D)
    v: jnp.ndarray,                   # (B, S, Hk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked online-softmax attention. Returns (B, S, Hq, D)."""
    B, S, Hq, D = q.shape
    Hk = k.shape[2]
    g = Hq // Hk
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} must be divisible by block sizes")
    q_blocks = S // block_q
    kv_blocks = S // block_k

    # (B, S, Hq, D) -> blocks of (1, block_q, g, D) per kv head
    qg = q.reshape(B, S, Hk, g, D)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks,
    )
    grid = (B, Hk, q_blocks, kv_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, g, D), lambda b, h, qi, ki: (b, qi, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, qi, ki: (b, ki, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, qi, ki: (b, ki, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, g, D), lambda b, h, qi, ki: (b, qi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, Hk, g, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * g, D), jnp.float32),
            pltpu.VMEM((block_q * g,), jnp.float32),
            pltpu.VMEM((block_q * g,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(B, S, Hq, D)


def flash_attention_trainable(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """Flash-attention forward (Pallas) with an oracle backward.

    The backward pass recomputes attention via the pure-jnp reference and
    differentiates it — numerically identical to the kernel's math.  A
    dedicated backward Pallas kernel (dq/dk/dv tiles with the saved
    logsumexp) is the production follow-up; this wrapper keeps the fused
    forward while remaining fully trainable."""
    from .ref import attention_ref

    @jax.custom_vjp
    def attn(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    def fwd(q, k, v):
        return attn(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal, window=window),
            q, k, v,
        )
        return vjp(g)

    attn.defvjp(fwd, bwd)
    return attn(q, k, v)
