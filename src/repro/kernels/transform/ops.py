"""Public op: shape-agnostic fused transform (pads/tiles to kernel layout)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import default_interpret
from .kernel import LANE, SUBLANE, fused_transform_2d
from .ref import fused_transform_ref
import functools


@functools.partial(jax.jit, static_argnames=("scale", "bias", "lo", "hi",
                                             "out_dtype"))
def fused_transform_xla(x, *, scale=1.0, bias=0.0, lo=-np.inf, hi=np.inf,
                        out_dtype=None):
    """Single-pass fused affine+clamp+cast compiled by XLA — the CPU
    wall-clock proxy for the Pallas kernel (which targets TPU and is
    validated in interpret mode)."""
    y = x.astype(jnp.float32) * scale + bias
    y = jnp.clip(y, lo, hi)
    return y.astype(out_dtype or x.dtype)


def fused_transform(x, *, scale: float = 1.0, bias: float = 0.0,
                    lo: float = -np.inf, hi: float = np.inf,
                    out_dtype=None, interpret: Optional[bool] = None):
    """Arbitrary-shape fused affine+clamp+cast via the Pallas kernel."""
    x = jnp.asarray(x)
    out_dtype = jnp.dtype(out_dtype) if out_dtype else x.dtype
    n = x.size
    if n == 0:
        return x.astype(out_dtype)
    cols = LANE
    rows = -(-n // cols)
    block_rows = 256
    # pad rows to a multiple of the grid block (grid must tile exactly)
    quantum = block_rows if rows > block_rows else SUBLANE
    rows_pad = -(-rows // quantum) * quantum
    if n % cols:
        x2 = jnp.pad(jnp.ravel(x), (0, rows * cols - n)).reshape(rows, cols)
    else:
        # pad whole rows: the TPU compiler takes minutes over a 1-D pad
        # of a frame-sized uint8 vector
        x2 = x.reshape(rows, cols)
    x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, 0)))
    y = fused_transform_2d(x2, scale=scale,
                           bias=bias, lo=float(lo), hi=float(hi),
                           out_dtype=out_dtype, block_rows=block_rows,
                           interpret=default_interpret(interpret))
    return jnp.ravel(y)[:n].reshape(x.shape)
