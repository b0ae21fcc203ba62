"""Single-token GQA decode attention as a Pallas TPU kernel.

Decode attention is memory-bound: one query token streams the whole KV
cache through VMEM once.  TPU-native design:
  * grid (batch, kv_head, kv_blocks), kv_blocks sequential ("arbitrary") so
    the online-softmax state rides in VMEM scratch — the classic GPU
    "split-K + second-pass reduce" becomes a sequential VMEM accumulation
    (no inter-core reduction needed on TPU; splitting across cores is the
    mesh's job via sequence-sharded caches, see launch/sharding.py);
  * all g = Hq/Hk grouped query heads share each streamed K/V tile — the
    GQA bandwidth saving is the whole point of the layout;
  * variable cache fill is handled by a per-batch ``lengths`` mask.

Validated in interpret mode against
:func:`repro.kernels.ref.decode_attention_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode"]

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_k: int, kv_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                          # (g, D)
    k = k_ref[0, :, 0, :]                    # (block_k, D)
    v = v_ref[0, :, 0, :]
    length = len_ref[0]

    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale                                 # (g, block_k)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(k_pos < length, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_prev * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode(
    q: jnp.ndarray,                # (B, Hq, D) single query token
    k: jnp.ndarray,                # (B, C, Hk, D) cache
    v: jnp.ndarray,                # (B, C, Hk, D)
    lengths: jnp.ndarray,          # (B,) int32 valid lengths
    *,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    C, Hk = k.shape[1], k.shape[2]
    g = Hq // Hk
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    block_k = min(block_k, C)
    if C % block_k:
        raise ValueError(f"cache size {C} not divisible by block_k={block_k}")
    kv_blocks = C // block_k

    qg = q.reshape(B, Hk, g, D)
    kernel = functools.partial(_kernel, scale=scale, block_k=block_k,
                               kv_blocks=kv_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hk, kv_blocks),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ki: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, ki: (b, ki, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, ki: (b, ki, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, g, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, Hq, D)
