"""Pallas TPU decode attention: flash-decoding style split-K.

One new token attends to a long KV cache (the decode_32k / long_500k hot
path).  Grid (B, H, n_kblocks): KV blocks stream HBM->VMEM while running
(m, l, acc) stay in VMEM scratch; the valid-length mask comes from a
scalar operand.  q is tiny ((1, hd) per head) so arithmetic intensity is
memory-bound by design — the kernel's job is to keep the KV stream at
HBM bandwidth, which on TPU means (block_k x hd) tiles with hd on lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _split_k_step(ik, n_k, q, k, v, kpos_limit, o_ref, m_ref, l_ref,
                  acc_ref, k_rows=None, v_rows=None):
    """One split-K step of online softmax over a streamed KV block.

    q: (1, hd) pre-scaled; k, v: (bk, hd) f32; positions at or past
    ``kpos_limit`` are masked.  ``k_rows``/``v_rows``, when given, are
    (1, bk) per-row scales of k and v, applied to the scores and to the
    probabilities instead of to the (bk, hd) tiles.  The running max and
    denominator live in (1, 1) VMEM scratch and are updated as (1, 1)
    vectors: the TPU cannot store a scalar to vector memory."""
    bk = k.shape[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    if k_rows is not None:
        s = s * k_rows
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kpos_limit, s, NEG)

    m_prev = m_ref[...]                                    # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p if v_rows is None else p * v_rows, v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (1, hd)
    acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale, n_k):
    _split_k_step(pl.program_id(2), n_k,
                  q_ref[0, 0].astype(jnp.float32) * scale,
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  len_ref[0], o_ref, m_ref, l_ref, acc_ref)


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale, n_pages):
    """Same online-softmax step as ``_decode_kernel``, but the KV block
    streamed at grid step (b, h, ip) is *indirected*: the BlockSpec
    index map reads ``pt_ref[b, ip]`` (scalar-prefetched page table) to
    pick the physical block, so the kernel walks each sequence's pages
    in logical order while the pool stays scattered in HBM.  Per-row
    lengths replace the shared scalar length."""
    _split_k_step(pl.program_id(2), n_pages,
                  q_ref[0, 0].astype(jnp.float32) * scale,
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  len_ref[pl.program_id(0)], o_ref, m_ref, l_ref, acc_ref)


def _paged_decode_quant_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
                               *, scale, n_pages):
    """``_paged_decode_kernel`` over int8 K/V pools: the streamed (bs, hd)
    int8 tiles are widened to f32 unscaled, and the page's (1, bs) rows
    of per-row scales multiply the scores (k) and the probabilities (v)
    -- ``(q . k_j) * ks_j`` and ``sum_j (p_j * vs_j) v_j`` -- so the
    dequantization is two (1, bs) products, not two (bs, hd) ones."""
    _split_k_step(pl.program_id(2), n_pages,
                  q_ref[0, 0].astype(jnp.float32) * scale,
                  k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32),
                  len_ref[pl.program_id(0)], o_ref, m_ref, l_ref, acc_ref,
                  k_rows=ks_ref[0], v_rows=vs_ref[0])


def _scratch(hd):
    return [pltpu.VMEM((1, 1), jnp.float32),     # running max
            pltpu.VMEM((1, 1), jnp.float32),     # running denominator
            pltpu.VMEM((1, hd), jnp.float32)]    # output accumulator


def _paged_call(kernel, q, pools, page_table, lengths, interpret,
                row_scales=()):
    """Grid (B, H, P) over pages; every array in ``pools`` has the
    physical block axis first and the KV head axis second, and streams
    the block ``page_table[b, ip]`` picks.  Each (num_blocks, KV, bs)
    array in ``row_scales`` streams that block's (1, bs) row for the KV
    head, from a (num_blocks * KV, 1, bs) view whose last two dims a
    (1, 1, bs) block spans whole."""
    B, H, hd = q.shape
    KV = pools[0].shape[1]
    P = page_table.shape[1]
    G = H // KV

    def page_spec(a):
        return pl.BlockSpec((1, 1) + a.shape[2:],
                            lambda b, h, ip, ln, pt: (pt[b, ip], h // G, 0, 0))

    def row_spec(bs):
        return pl.BlockSpec(
            (1, 1, bs),
            lambda b, h, ip, ln, pt: (pt[b, ip] * KV + h // G, 0, 0))

    rows = [r.reshape(r.shape[0] * KV, 1, r.shape[2]) for r in row_scales]
    out = pl.pallas_call(
        functools.partial(kernel, scale=1.0 / np.sqrt(hd), n_pages=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, P),
            in_specs=[pl.BlockSpec((1, 1, 1, hd),
                                   lambda b, h, ip, ln, pt: (b, h, 0, 0))]
            + [page_spec(a) for a in pools]
            + [row_spec(r.shape[2]) for r in rows],
            out_specs=pl.BlockSpec((1, 1, 1, hd),
                                   lambda b, h, ip, ln, pt: (b, h, 0, 0)),
            scratch_shapes=_scratch(hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32).reshape(B),
      jnp.asarray(page_table, jnp.int32), q[:, :, None, :], *pools, *rows)
    return out[:, :, 0, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                                 page_table, lengths, *, interpret: bool):
    """Int8 variant of ``paged_decode_attention``.

    q: (B,H,hd) float; pools: (num_blocks,KV,bs,hd) int8;
    k_scale/v_scale: (num_blocks,KV,bs) float32 per-row scales;
    page_table: (B,P) int32; lengths: (B,) int32 -> (B,H,hd).

    Same split-K page walk; each page's int8 (bs, hd) tiles arrive with
    their KV head's (1, bs) row of k and v scales, streamed through
    their own page-table-indirected BlockSpecs.  Compiled for a v5e,
    the row view is laid out in (1, 128) tiles: 512 bytes per page and
    KV head for bs=16, half of the page's 1 KiB int8 (16, 64) K tile.
    The kernel's HBM traffic has not been measured on a chip.
    """
    return _paged_call(_paged_decode_quant_kernel, q, [k_pages, v_pages],
                       page_table, lengths, interpret,
                       row_scales=[k_scale, v_scale])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           interpret: bool):
    """q: (B,H,hd); pools: (num_blocks,KV,bs,hd); page_table: (B,P)
    int32; lengths: (B,) int32 -> (B,H,hd).

    Flash-decoding split-K over *pages*: grid (B, H, P), one KV block
    per page.  Unallocated page-table entries may point anywhere valid —
    their positions exceed ``lengths`` so the mask zeroes them.
    """
    return _paged_call(_paged_decode_kernel, q, [k_pages, v_pages],
                       page_table, lengths, interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, block_k: int = 512,
                     interpret: bool):
    """q: (B,H,hd); caches: (B,KV,C,hd); length: () int32 -> (B,H,hd)."""
    B, H, hd = q.shape
    KV, C = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bk = min(block_k, C)
    n_k = C // bk
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / np.sqrt(hd), n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik, ln: (b, h // G, ik, 0)),
                pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik, ln: (b, h // G, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik, ln: (b, h, 0, 0)),
            scratch_shapes=_scratch(hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32).reshape(1), q[:, :, None, :],
      k_cache, v_cache)
    return out[:, :, 0, :]
