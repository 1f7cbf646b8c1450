"""Pallas kernel: fused MoE top-k gating.

One VMEM pass per token block: iteratively extract the k maxima
(k <= 8 everywhere in the assigned pool) instead of sorting E scores.
E is small (16-256) so a block of scores (block_t, E) sits in VMEM and
the k passes are VPU-only — no HBM re-reads per pass, which is the point
of fusing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _gating_kernel(s_ref, vals_ref, idx_ref, *, k):
    s = s_ref[...].astype(jnp.float32)            # (bt, E)
    bt, E = s.shape
    eidx = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    for j in range(k):                            # k static, small
        m = jnp.max(s, axis=1)                    # (bt,)
        # first argmax position
        is_max = (s == m[:, None])
        first = jnp.min(jnp.where(is_max, eidx, E), axis=1)
        vals_ref[:, j] = m
        idx_ref[:, j] = first
        s = jnp.where(eidx == first[:, None], NEG, s)


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def gating_topk(scores, k: int, *, block_t: int = 512, interpret: bool):
    """scores: (T, E), T multiple of block_t -> (vals (T,k), idx (T,k))."""
    T, E = scores.shape
    bt = min(block_t, T)
    grid = (T // bt,)
    return pl.pallas_call(
        functools.partial(_gating_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((T, k), jnp.float32),
                   jax.ShapeDtypeStruct((T, k), jnp.int32)),
        interpret=interpret,
    )(scores)
