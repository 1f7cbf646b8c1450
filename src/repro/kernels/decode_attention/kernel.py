"""Pallas TPU attention over KV caches: flash-decoding style split-K.

``paged_attention`` is the serving engine's attention for both
megasteps: T query tokens per slot (1 in a decode burst, the prefill
chunk in a mixed step) against the slot's live pages of the block pool,
read in place through the page table.  ``decode_attention`` (one token
over a dense cache) and ``paged_decode_attention_quant`` (one token
over the int8 pool, grid (B, H, P), not on the served path yet) keep
the older one-page-per-grid-step form: KV blocks stream HBM->VMEM while
running (m, l, acc) stay in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# pages ``paged_attention`` copies in and scores per loop step, 512
# positions of 16-token pages: on a TPU v5e, of 8, 16, 32 and 64, 32
# was the fastest or within 8% of it at each of four smollm-360m loads
PAGES_PER_STEP = 32


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


def _paged_decode_quant_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
                               *, scale, n_pages):
    """One decode token over int8 K/V pools, one page per grid step
    (b, h, ip), the page picked by the scalar-prefetched page table in
    the BlockSpec's index map: the streamed (bs, hd) int8 tiles are widened to f32 unscaled, and the page's (1, bs) rows
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
    """One decode token per slot over the int8 block pool.

    q: (B,H,hd) float; pools: (num_blocks,KV,bs,hd) int8;
    k_scale/v_scale: (num_blocks,KV,bs) float32 per-row scales;
    page_table: (B,P) int32; lengths: (B,) int32 -> (B,H,hd).

    Split-K over pages, grid (B, H, P): every page of the page table
    streams in, live or not, and the length mask zeroes the dead ones
    (``paged_attention`` reads only live pages; this kernel is to move
    onto it).  Each page's int8 (bs, hd) tiles arrive with
    their KV head's (1, bs) row of k and v scales, streamed through
    their own page-table-indirected BlockSpecs.  Compiled for a v5e,
    the row view is laid out in (1, 128) tiles: 512 bytes per page and
    KV head for bs=16, half of the page's 1 KiB int8 (16, 64) K tile.
    The kernel's HBM traffic has not been measured on a chip.
    """
    return _paged_call(_paged_decode_quant_kernel, q, [k_pages, v_pages],
                       page_table, lengths, interpret,
                       row_scales=[k_scale, v_scale])


def _paged_attention_kernel(lay_ref, len_ref, tv_ref, pt_ref, rowt_ref,
                            q_ref, k_hbm, v_hbm, o_ref,
                            k_buf, v_buf, sem, m_ref, l_ref, acc_ref):
    """One slot, every KV head, all its G*T query rows.

    Slot b's live extent is ``lengths[b] + t_valid[b]`` positions, so it
    reads pages ``0 .. ceil(extent / bs) - 1`` of its page table and no
    other: ``PAGES_PER_STEP`` pages at a time, each one DMA of its
    (KV, rows, lanes) block straight from the layer's pool,
    double-buffered against the online softmax of the step before.

    A page row holds ``f = lanes // hd`` consecutive positions side by
    side (see ``models.attention.paged_page_shape``), so q arrives as f
    copies, copy g holding the query in lane group g and zeros in the
    others: copy g's scores are those of positions ``f*j + g``.  Query
    row r (token ``rowt[r]``) sees positions ``<= lengths[b] + rowt[r]``.
    A slot with ``t_valid == 0`` reads nothing and writes zeros."""
    b = pl.program_id(0)
    layer = lay_ref[0]
    _, KV, f, R, W = q_ref.shape
    hd = o_ref.shape[-1]
    rows = k_hbm.shape[3]
    bs = rows * f
    P = pt_ref.shape[1]
    n_rows = PAGES_PER_STEP * rows
    length = len_ref[b]
    extent = length + tv_ref[b]
    n_pages = jnp.where(tv_ref[b] > 0,
                        jnp.minimum((extent + bs - 1) // bs, P), 0)
    n_steps = (n_pages + PAGES_PER_STEP - 1) // PAGES_PER_STEP

    @pl.when(b == 0)
    def _zero_buffers():
        # pages past a slot's extent are never copied in: their rows of
        # the buffer keep what an earlier step left there, which the
        # mask zeroes in the probabilities -- so it must be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def each_page(step, half, op):
        """``op`` on the K and V copies of step ``step``'s live pages."""
        def one(i, carry):
            blk = pt_ref[b, step * PAGES_PER_STEP + i]
            dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
            for j, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                op(pltpu.make_async_copy(hbm.at[layer, blk],
                                         buf.at[half, :, dst, :],
                                         sem.at[half, j]))
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(PAGES_PER_STEP, n_pages - step * PAGES_PER_STEP),
            one, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_steps > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    limit = length + rowt_ref[...]                        # (R, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, n_rows), 1) * f
    group = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1) // hd

    def body(step, carry):
        half = step % 2

        @pl.when(step + 1 < n_steps)
        def _next():
            each_page(step + 1, 1 - half, lambda c: c.start())

        each_page(step, half, lambda c: c.wait())
        live = []
        for g in range(f):
            kpos = step * (PAGES_PER_STEP * bs) + col + g
            live.append((kpos <= limit) & (kpos < extent))
        for h in range(KV):
            k = k_buf[half, h]
            v = v_buf[half, h]
            s = [jnp.where(live[g], jax.lax.dot_general(
                     q_ref[0, h, g], k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32), NEG)
                 for g in range(f)]
            m_prev = m_ref[h]
            m_new = m_prev
            for sg in s:
                m_new = jnp.maximum(m_new, jnp.max(sg, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h] * corr
            acc = acc_ref[h] * corr
            for g, sg in enumerate(s):
                p = jnp.exp(sg - m_new)
                l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
                pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc = acc + (pv if f == 1 else jnp.where(group == g, pv, 0.0))
            m_ref[h] = m_new
            l_ref[h] = l_new
            acc_ref[h] = acc
        return carry

    jax.lax.fori_loop(0, n_steps, body, 0)
    for h in range(KV):
        acc = acc_ref[h]
        out = acc
        for g in range(1, f):        # fold lane group g onto group 0
            out = out + pltpu.roll(acc, W - g * hd, 1)
        l = l_ref[h]
        out = jnp.where(l > 0, out / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, h] = out[:, :hd].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, lengths, t_valid, layer,
                    *, interpret: bool):
    """Attention of T query tokens per slot over its live pages, read in
    place from the block pool.

    q: (B, T, H, hd); pools: (L, num_blocks, KV, rows, lanes) with
    ``rows * lanes = block_size * hd`` (``paged_page_shape``), of which
    layer ``layer`` is read; page_table: (B, P) int32; lengths: (B,)
    positions cached before this step; t_valid: (B,) tokens of this step
    that are real.  Query t of slot b sees positions
    ``<= lengths[b] + t``.  Returns (B, T, H, hd) in the pool's dtype.

    Grid (B,): one slot per step, all KV heads, every page read once for
    all G*T query rows of its head.  Only pages below
    ``ceil((lengths + t_valid) / bs)`` are copied in, so a step costs
    what the slots hold, not what the page table could address.  Scores,
    the online softmax and the accumulators are float32; probabilities
    meet V in the pool's dtype.  Rows past ``t_valid`` see the slot's
    whole extent and are the caller's to ignore.
    """
    B, T, H, hd = q.shape
    KV, rows, W = k_pages.shape[2:]
    G, f = H // KV, W // hd
    R = T * G
    # (B, T, KV, G, hd) -> (B, KV, T*G, hd): row t*G + g is head kv*G + g
    qk = jnp.swapaxes((q * (1.0 / np.sqrt(hd))).astype(k_pages.dtype)
                      .reshape(B, T, KV, G, hd), 1, 2).reshape(B, KV, R, hd)
    # copy g of q sits in lane group g of the folded rows, zeros elsewhere
    qk = jnp.stack([jnp.pad(qk, ((0, 0),) * 3 + ((g * hd, W - (g + 1) * hd),))
                    for g in range(f)], axis=2)           # (B, KV, f, R, W)
    rowt = (jnp.arange(R, dtype=jnp.int32) // G).reshape(R, 1)
    n_rows = PAGES_PER_STEP * rows
    out = pl.pallas_call(
        _paged_attention_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((R, 1), lambda b, *_: (0, 0)),
                      pl.BlockSpec((1, KV, f, R, W),
                                   lambda b, *_: (b, 0, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, KV, R, hd), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KV, n_rows, W), k_pages.dtype),
                pltpu.VMEM((2, KV, n_rows, W), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((KV, R, 1), jnp.float32),
                pltpu.VMEM((KV, R, 1), jnp.float32),
                pltpu.VMEM((KV, R, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), k_pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(lengths, jnp.int32).reshape(B),
      jnp.asarray(t_valid, jnp.int32).reshape(B),
      jnp.asarray(page_table, jnp.int32), rowt, qk, k_pages, v_pages)
    return jnp.swapaxes(out.reshape(B, KV, T, G, hd), 1, 2).reshape(
        B, T, H, hd)


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
