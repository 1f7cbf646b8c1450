"""Pallas TPU flash attention (prefill): blockwise online softmax.

Grid (B, H, nq, nk), innermost kv dim sequential on TPU; running
(m, l, acc) live in VMEM scratch across kv steps.  Q/K/V tiles are
(block_q x hd) / (block_k x hd) — hd is 64..192 in the assigned pool, so
tiles are MXU-aligned on the lane dim and the two matmuls per step hit
the MXU.  GQA maps query head -> kv head in the BlockSpec index_map (no
materialized K/V repeat).  Causal + sliding-window masks are applied from
global block offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, block_q, block_k, n_k, causal, window):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[:, 0]                                  # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
    m_ref[:, 0] = m_new
    v = v_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, 0], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool):
    """q: (B,H,S,hd); k,v: (B,KV,T,hd).  S % block_q == T % block_k == 0."""
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, S), min(block_k, T)
    nq, nk = S // bq, T // bk
    scale = 1.0 / np.sqrt(hd)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=bq, block_k=bk,
                          n_k=nk, causal=causal, window=sliding_window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
