"""Pallas kernel: fused typecast+scale+bias+clamp (Tensor-Transform).

The NNStreamer tensor_transform chain (e.g. "typecast:float32,
divide:255,subtract:0.5") is one elementwise affine op after folding;
on TPU we fuse it into a single HBM->VMEM->HBM pass with (8,128)-aligned
tiles instead of one pass per chain op (paper E4's pre-processing
overhead, adapted to the TPU memory hierarchy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8


def _widened(dtype):
    """Mosaic casts unsigned integers to and from float only by way of
    int32 (uint8 -> float32 is refused)."""
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return jnp.int32
    return dtype


def _transform_kernel(x_ref, o_ref, *, scale, bias, lo, hi):
    x = x_ref[...]
    x = x.astype(_widened(x.dtype)).astype(jnp.float32)
    y = x * scale + bias
    y = jnp.clip(y, lo, hi)
    o_ref[...] = y.astype(_widened(o_ref.dtype)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bias", "lo", "hi",
                                             "out_dtype", "block_rows",
                                             "interpret"))
def fused_transform_2d(x, *, scale: float, bias: float, lo: float, hi: float,
                       out_dtype=None, block_rows: int = 256,
                       interpret: bool):
    """x: (R, C) with C a multiple of 128; R a multiple of 8."""
    R, C = x.shape
    out_dtype = out_dtype or x.dtype
    br = min(block_rows, R)
    grid = (R // br,)
    return pl.pallas_call(
        functools.partial(_transform_kernel, scale=scale, bias=bias,
                          lo=lo, hi=hi),
        grid=grid,
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(x)
