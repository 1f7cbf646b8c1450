"""Public op: padding + dtype handling for the selective-scan kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import default_interpret
from .kernel import LANES, ROWS, selective_scan_kernel


def selective_scan(dt, Bc, Cc, xs, A, D, h0=None, *, block_d: int = 128,
                   chunk_t: int = 256, interpret: Optional[bool] = None):
    """Same contract as models.mamba.selective_scan (h0 must be None —
    prefill starts cold; decode uses the single-step jnp path)."""
    assert h0 is None, "kernel path supports cold start only"
    B, S, di = xs.shape
    bd = min(block_d, di)
    # whole aligned row groups, and whole lane tiles past one; padded
    # steps have dt = 0, which leaves the state untouched
    ct = -(-min(chunk_t, S) // ROWS) * ROWS
    if ct > LANES:
        ct = -(-ct // LANES) * LANES
    pad_d = (-di) % bd
    pad_t = (-S) % ct
    if pad_d:
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_d)))
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pad_d)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
        D = jnp.pad(D, (0, pad_d))
    if pad_t:
        dt = jnp.pad(dt, ((0, 0), (0, pad_t), (0, 0)))
        xs = jnp.pad(xs, ((0, 0), (0, pad_t), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad_t), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad_t), (0, 0)))
    y, h_last = selective_scan_kernel(dt, xs, Bc, Cc, A, D, block_d=bd,
                                      chunk_t=ct,
                                      interpret=default_interpret(interpret))
    y = y[:, :S, :di]
    h_last = h_last[:, :di]
    return y, h_last
