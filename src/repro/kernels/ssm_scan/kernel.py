"""Pallas TPU selective scan (Mamba S6).

TPU adaptation of the CUDA selective-scan: grid (B, n_d, n_t) with the
time dim innermost-sequential; the recurrent state lives in VMEM
scratch across time chunks, dt/x/B/C stream in per-chunk.  This
replaces warp-level shuffles with VMEM-resident state, trading GPU
shared-memory tricks for TPU's large vector memory.

The state is kept transposed, (N, block_d), so that a time step's dt
and x rows broadcast down the sublanes as they are loaded.  dt and x
are read ``ROWS`` rows at a time at aligned offsets (the chip refuses a
load at a row it cannot prove tile-aligned), and y is written back the
same way.  B and C arrive transposed, (N, chunk_t); a row group loads
the aligned ``LANES``-wide slice that holds its columns, and each step
takes its column with a masked reduction over that slice: 2 * N *
LANES multiply-adds a step beside the N * block_d of the state update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 16    # rows per aligned load: one packed bf16 sublane tile
LANES = 128  # columns of B^T/C^T per aligned load: one lane tile


def _ssm_kernel(dt_ref, x_ref, bt_ref, ct_ref, at_ref, d_ref, y_ref,
                h_out_ref, h_ref, *, chunk_t, n_t):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    At = at_ref[...].astype(jnp.float32)                   # (N, bd)
    Dp = d_ref[...].astype(jnp.float32)                    # (1, bd)
    width = min(chunk_t, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (At.shape[0], width), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, At.shape[1]), 0)

    def rows(g, h):
        r0 = pl.multiple_of(g * ROWS, ROWS)
        c0 = pl.multiple_of(r0 // width * width, width)
        dts = dt_ref[0, pl.ds(r0, ROWS), :].astype(jnp.float32)  # (ROWS, bd)
        xs = x_ref[0, pl.ds(r0, ROWS), :].astype(jnp.float32)
        Bt = bt_ref[0, :, pl.ds(c0, width)].astype(jnp.float32)  # (N, width)
        Ct = ct_ref[0, :, pl.ds(c0, width)].astype(jnp.float32)
        ys = jnp.zeros(row.shape, jnp.float32)
        for i in range(ROWS):
            dt_t = dts[i:i + 1]                                # (1, bd)
            x_t = xs[i:i + 1]
            at_t = lane == r0 - c0 + i
            b_t = jnp.sum(jnp.where(at_t, Bt, 0.0), axis=1, keepdims=True)
            c_t = jnp.sum(jnp.where(at_t, Ct, 0.0), axis=1, keepdims=True)
            h = jnp.exp(dt_t * At) * h + (dt_t * x_t) * b_t    # (N, bd)
            y_t = jnp.sum(h * c_t, axis=0, keepdims=True) + Dp * x_t
            ys = jnp.where(row == i, y_t, ys)
        y_ref[0, pl.ds(r0, ROWS), :] = ys.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk_t // ROWS, rows, h_ref[...])
    h_ref[...] = h

    @pl.when(it == n_t - 1)
    def _emit_state():
        h_out_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_d", "chunk_t", "interpret"))
def selective_scan_kernel(dt, xs, Bc, Cc, A, D, *, block_d: int = 128,
                          chunk_t: int = 256, interpret: bool):
    """dt, xs: (B,S,di); Bc, Cc: (B,S,N); A: (di,N); D: (di,).

    S % chunk_t == 0, chunk_t % ROWS == 0, chunk_t <= LANES or chunk_t
    % LANES == 0, and di % block_d == 0 (ops.py pads).  Returns (y (B,S,di), h_last (B,di,N) f32).
    """
    B, S, di = xs.shape
    N = Bc.shape[-1]
    bd = min(block_d, di)
    ct = min(chunk_t, S)
    n_d, n_t = di // bd, S // ct
    y, h_last = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk_t=ct, n_t=n_t),
        grid=(B, n_d, n_t),
        in_specs=[
            pl.BlockSpec((1, ct, bd), lambda b, id_, it: (b, it, id_)),  # dt
            pl.BlockSpec((1, ct, bd), lambda b, id_, it: (b, it, id_)),  # x
            pl.BlockSpec((1, N, ct), lambda b, id_, it: (b, 0, it)),     # B^T
            pl.BlockSpec((1, N, ct), lambda b, id_, it: (b, 0, it)),     # C^T
            pl.BlockSpec((N, bd), lambda b, id_, it: (0, id_)),          # A^T
            pl.BlockSpec((1, bd), lambda b, id_, it: (0, id_)),          # D
        ],
        out_specs=(
            pl.BlockSpec((1, ct, bd), lambda b, id_, it: (b, it, id_)),  # y
            pl.BlockSpec((1, N, bd), lambda b, id_, it: (b, 0, id_)),    # h^T
        ),
        out_shape=(jax.ShapeDtypeStruct((B, S, di), xs.dtype),
                   jax.ShapeDtypeStruct((B, N, di), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(dt, xs, jnp.swapaxes(Bc, 1, 2), jnp.swapaxes(Cc, 1, 2), A.T,
      D.reshape(1, di))
    return y, jnp.swapaxes(h_last, 1, 2)
