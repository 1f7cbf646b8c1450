"""Attention: GQA/MHA (RoPE / M-RoPE / partial rotary / sliding window)
and DeepSeek-V3 MLA (multi-head latent attention).

Three execution paths:
  * naive    — materialize (q, k) score matrix (small seq)
  * chunked  — lax.scan over KV blocks with online softmax (memory-bounded;
               the pure-XLA analogue of flash attention for long prefill)
  * decode   — single query token against a KV cache (full or ring-buffer
               sliding window)

Shapes: hidden (B, S, D); q/k/v (B, S, H, hd).  GQA repeats KV heads by
group broadcast (no materialized repeat: einsum over grouped heads).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import apply_mrope, apply_rope, dense_init
from .config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def gqa_params(key, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h * hd, d), in_axis=0, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def mla_params(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wdq": dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), dtype)},
        "wuq": dense_init(ks[1], (m.q_lora_rank, h * qk_head), dtype=dtype),
        "wdkv": dense_init(ks[2], (d, m.kv_lora_rank), dtype=dtype),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dtype)},
        "wkr": dense_init(ks[3], (d, m.qk_rope_head_dim), dtype=dtype),
        "wuk": dense_init(ks[4], (m.kv_lora_rank, h * m.qk_nope_head_dim), dtype=dtype),
        "wuv": dense_init(ks[5], (m.kv_lora_rank, h * m.v_head_dim), dtype=dtype),
        "wo": dense_init(ks[6], (h * m.v_head_dim, d), dtype=dtype),
    }


# ---------------------------------------------------------------------------
# score computation cores
# ---------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q: (B,S,H,hd) k: (B,T,KV,hd) -> (B, KV, G, S, T) with H = KV*G."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k)


def _grouped_out(probs, v):
    """probs: (B,KV,G,S,T) v: (B,T,KV,hd) -> (B,S,H,hd)."""
    B, KV, G, S, T = probs.shape
    hd = v.shape[-1]
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, KV * G, hd)


def naive_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_len: Optional[jnp.ndarray] = None,
                    sliding_window: int = 0, scale: Optional[float] = None):
    """Full-score attention.  q:(B,S,H,hd) k,v:(B,T,KV,hd_{k,v})."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    scores = _grouped_scores(q * scale, k).astype(jnp.float32)  # (B,KV,G,S,T)
    q_pos = jnp.arange(S)[:, None] + q_offset
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window:
        mask &= k_pos > q_pos - sliding_window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_len is not None:  # (B,) valid lengths in cache
        valid = k_pos < kv_len[:, None]
        scores = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return _grouped_out(probs, v)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                      sliding_window: int = 0, scale: Optional[float] = None,
                      remat: bool = False, unroll: bool = False,
                      acc_bf16: bool = False, probs_bf16: bool = False):
    """Two-level blockwise attention (flash-style, pure XLA).

    Outer scan over q chunks, inner scan over kv chunks with online
    softmax.  With ``remat`` the q-chunk body is checkpointed so the
    backward pass never holds more than one q-chunk's score blocks —
    the memory shape that makes 32k-seq training lower within HBM.
    (On real TPU the Pallas flash kernel replaces this; this is the
    GSPMD-partitionable fallback with the same asymptotics.)
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    hv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    ck = min(chunk, T)
    cq = min(chunk, S)
    if unroll:  # bound HLO size: at most 8x8 unrolled blocks
        ck = max(ck, -(-T // 8))
        cq = max(cq, -(-S // 8))
    nk = -(-T // ck)
    nq = -(-S // cq)
    pad_k = nk * ck - T
    pad_q = nq * cq - S
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(B, nk, ck, KV, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, ck, KV, hv), 1, 0)
    qg = jnp.moveaxis((q * scale).reshape(B, nq, cq, KV, G, hd), 1, 0)

    def q_body(carry, q_xs):
        qb, iq = q_xs                                # (B,cq,KV,G,hd)
        q_pos = iq * cq + jnp.arange(cq)[:, None]

        def kv_body(inner, xs):
            m, l, acc = inner
            kb, vb, ik = xs                          # (B,ck,KV,hd)
            s = jnp.einsum("bskgd,btkd->bkgst", qb, kb).astype(jnp.float32)
            k_pos = ik * ck + jnp.arange(ck)[None, :]
            mask = k_pos < T
            if causal:
                mask &= k_pos <= q_pos
            if sliding_window:
                mask &= k_pos > q_pos - sliding_window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            if probs_bf16:  # halve softmax-prob HBM traffic (doc'd error)
                p = p.astype(jnp.bfloat16)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1).astype(jnp.float32)
            pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(vb.dtype), vb)
            acc_new = acc * corr[..., None].astype(acc.dtype) + \
                pv.astype(acc.dtype)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        acc0 = jnp.zeros((B, KV, G, cq, hv),
                         jnp.bfloat16 if acc_bf16 else v.dtype)
        if unroll:
            inner = (m0, l0, acc0)
            for ik in range(nk):
                inner, _ = kv_body(inner, (kc[ik], vc[ik], jnp.int32(ik)))
            m, l, acc = inner
        else:
            (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, acc0),
                                          (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return carry, jnp.moveaxis(out.reshape(B, KV * G, cq, hv), 1, 2)

    if unroll:
        # Python-loop variant: every block lands in the HLO, so
        # cost_analysis counts true totals (XLA visits while bodies once)
        outs = []
        for iq in range(nq):
            _, o = q_body(0.0, (qg[iq], jnp.int32(iq)))
            outs.append(o)
        out = jnp.concatenate(outs, axis=1)
        return out[:, :S]
    body = q_body
    if remat:
        body = jax.checkpoint(q_body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(body, 0.0, (qg, jnp.arange(nq)))  # (nq,B,cq,H,hv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * cq, H, hv)
    return out[:, :S]


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Per-layer stacked cache.  k/v: (L, B, C, KV, hd); length: (B,)."""
    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray           # current fill (same for all b in batch)
    window: int = 0               # 0 = full cache; else ring buffer size

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def init_kv_cache(cfg: ModelConfig, n_attn_layers: int, batch: int,
                  capacity: int, window: int = 0, dtype=jnp.bfloat16,
                  k_dim: Optional[int] = None, v_dim: Optional[int] = None,
                  kv_heads: Optional[int] = None) -> KVCache:
    kv = kv_heads if kv_heads is not None else cfg.n_kv_heads
    hd_k = k_dim if k_dim is not None else cfg.resolved_head_dim
    hd_v = v_dim if v_dim is not None else cfg.resolved_head_dim
    cap = min(capacity, window) if window else capacity
    return KVCache(
        k=jnp.zeros((n_attn_layers, batch, cap, kv, hd_k), dtype),
        v=jnp.zeros((n_attn_layers, batch, cap, kv, hd_v), dtype),
        length=jnp.zeros((batch,), jnp.int32),
        window=window,
    )


def cache_update_one(k_cache, v_cache, k_new, v_new, pos, window: int):
    """Insert one token at `pos` (ring index if window).  k_cache:(B,C,KV,hd)."""
    cap = k_cache.shape[1]
    idx = jnp.mod(pos, cap) if window else pos
    k_cache = _dynamic_token_update(k_cache, k_new, idx)
    v_cache = _dynamic_token_update(v_cache, v_new, idx)
    return k_cache, v_cache


def _dynamic_token_update(cache, new, idx):
    """cache: (B, C, KV, hd); new: (B, 1, KV, hd); idx scalar."""
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (0, idx, 0, 0))


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: Optional[float] = None):
    """One-token attention over the cache.

    q: (B,1,H,hd); caches (B,C,KV,hd); pos = tokens generated so far
    (the new token's position).  With a ring buffer (window), all slots
    are valid once pos >= capacity; masking handles partial fill.
    """
    B, _, H, hd = q.shape
    C = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    scores = _grouped_scores(q * scale, k_cache).astype(jnp.float32)  # (B,KV,G,1,C)
    slot = jnp.arange(C)[None, :]
    n_valid = jnp.minimum(pos + 1, C)  # includes the just-inserted token
    valid = slot < n_valid
    scores = jnp.where(valid[:, None, None, None] if valid.ndim == 2
                       else valid[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return _grouped_out(probs, v_cache)


LANES = 128


def paged_page_shape(block_size: int, head_dim: int) -> Tuple[int, int]:
    """(rows, lanes) of one KV head's page in the block pool.

    A page holds the head's (block_size, head_dim) rows in row-major
    order.  A head narrower than 128 is folded onto 128 lanes, ``128 //
    head_dim`` positions to a row, when the page fills whole rows: the
    TPU then lays the pool out row-major and unpadded, as the paged
    attention kernel copies it, where a 64-wide minor dim would be
    padded to 128 or moved off the lanes by the device's default
    layout.  Otherwise the page is (block_size, head_dim) as it is."""
    if (head_dim < LANES and LANES % head_dim == 0
            and (block_size * head_dim) % LANES == 0):
        return block_size * head_dim // LANES, LANES
    return block_size, head_dim


def paged_gather(storage, page_table, layer=None):
    """Materialize per-slot logical views of a shared block pool.

    storage: (num_blocks, KV, block_size, ...), or (L, num_blocks, KV,
    block_size, ...) with ``layer`` picking one layer's pool (a K/V pool
    of folded pages is passed unfolded, ``(..., block_size, hd)``);
    page_table: (B, P) int32.  Returns (B, P * block_size, KV, ...) —
    row ``b`` holds slot ``b``'s logical positions 0..P*bs-1 in order.
    Entries past the slot's true length are whatever the pointed-to
    blocks hold; callers mask by length.
    """
    B, P = page_table.shape
    g = storage[page_table] if layer is None \
        else storage[layer, page_table]           # (B, P, KV, bs, ...)
    g = jnp.swapaxes(g, 2, 3)                     # (B, P, bs, KV, ...)
    return g.reshape((B, P * g.shape[2]) + g.shape[3:])


def paged_scatter(storage, vals, page_table, lengths, t_valid, layer=None):
    """Write per-slot token runs into the shared block pool, in place.

    storage: a K/V pool (num_blocks, KV, rows, lanes) of
    ``paged_page_shape`` pages with vals (B, T, KV, hd), or a scale pool
    (num_blocks, KV, block_size) with vals (B, T, KV); either with a
    leading layer axis when ``layer`` names the layer written.  Token
    ``t`` of row ``b`` lands at logical position ``lengths[b] + t`` iff
    ``t < t_valid[b]``; invalid tokens (padding, inactive slots,
    positions past the page table) are dropped, not written.

    The write is page by page: the few pages a run touches are read,
    its positions merged in, and each page written back whole at a
    one-dimensional index -- one scatter op on the device with a few
    updates a slot, where a row or a position at a time would be
    hundreds.  A page no run touches is not written, and a page one
    run writes is that slot's own (shared pages are forked before the
    step), so no two updates meet.
    """
    lead = 0 if layer is None else 1
    nb, KV = storage.shape[lead:lead + 2]
    B, T = vals.shape[:2]
    P = page_table.shape[1]
    page_shape = storage.shape[lead + 1:]                # (KV, rows[, lanes])
    bs = int(np.prod(page_shape[1:])) // int(np.prod(vals.shape[3:]))
    flat = storage.reshape((-1,) + page_shape)           # (L*nb, KV, ...)
    # the pages a run of T positions can touch, and each one's positions
    n_pg = (T + bs - 2) // bs + 1
    page = lengths[:, None] // bs + jnp.arange(n_pg, dtype=jnp.int32)[None]
    tok = (page[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)
           - lengths[:, None, None])                     # (B, n_pg, bs)
    new = (tok >= 0) & (tok < t_valid[:, None, None])
    block = jnp.take_along_axis(page_table, jnp.clip(page, 0, P - 1), axis=1)
    if layer is not None:
        block = block + layer * nb
    at = jnp.where(new.any(-1) & (page < P), block, flat.shape[0])
    # the pages as (B, n_pg, KV, bs, ...) with the run's values merged in
    old = flat[jnp.clip(at, 0, flat.shape[0] - 1)]
    old = old.reshape((B, n_pg, KV, bs) + vals.shape[3:])
    val = jnp.take_along_axis(
        vals, jnp.clip(tok, 0, T - 1).reshape(
            (B, n_pg * bs) + (1,) * (vals.ndim - 2)), axis=1)
    val = jnp.swapaxes(val.reshape((B, n_pg, bs) + vals.shape[2:]), 2, 3)
    keep = new.reshape((B, n_pg, 1, bs) + (1,) * (vals.ndim - 3))
    pages = jnp.where(keep, val.astype(storage.dtype), old)
    flat = flat.at[at.reshape(-1)].set(
        pages.reshape((B * n_pg,) + page_shape), mode="drop")
    return flat.reshape(storage.shape)


def _unfold(store, hd):
    """A K/V pool of folded pages as (..., block_size, hd)."""
    return store.reshape(store.shape[:-2] + (-1, hd))


def paged_attention_path(pool_dtype) -> str:
    """Which path serves ``gqa_paged_step`` here, decided from what the
    trace can observe: ``"pallas"`` (``kernels.decode_attention``'s
    ``paged_attention``, reading live pages in place) on a TPU backend
    with no mesh active and a float pool; ``"jnp"`` (gather the whole
    page table, then ``paged_attention``) otherwise — on CPU, under a
    mesh, whose head_dim-sharded pool the kernel does not split, and
    for the int8 pool."""
    from .sharding import _context_mesh
    if (jax.default_backend() == "tpu" and _context_mesh() is None
            and jnp.issubdtype(pool_dtype, jnp.floating)):
        return "pallas"
    return "jnp"


def paged_attention(q, k_gath, v_gath, positions, *,
                    scale: Optional[float] = None):
    """Per-slot attention over page-table-gathered caches.

    q: (B,T,H,hd) — T query tokens per slot; k_gath/v_gath: (B,C,KV,hd)
    logical views from ``paged_gather``; positions: (B,T) each query's
    absolute position in its own sequence.  Query t of slot b attends
    to logical slots l <= positions[b, t] — per-slot causal masking with
    true lengths, no shared-position left padding.  For T=1 this is the
    same einsum/mask/softmax chain as ``decode_attention``, so paged
    and dense decode agree bit-for-bit on identical cache content.
    """
    hd = q.shape[-1]
    C = k_gath.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    scores = _grouped_scores(q * scale, k_gath).astype(jnp.float32)  # (B,KV,G,T,C)
    mask = jnp.arange(C)[None, None, :] <= positions[:, :, None]     # (B,T,C)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_gath.dtype)
    return _grouped_out(probs, v_gath)


def gqa_paged_step(p, cfg: ModelConfig, x, k_store, v_store, page_table,
                   lengths, t_valid, layer=None):
    """Process T tokens per slot through a block-paged KV cache.

    x: (B,T,D); k_store/v_store: (num_blocks, KV, rows, lanes) shared
    pools of ``paged_page_shape`` pages, or the (L, ...) stack of every
    layer's pools with ``layer`` the one this call reads and writes (so
    the stack is updated in place, never sliced out and written back);
    page_table: (B,P) int32; lengths: (B,) tokens already cached per
    slot; t_valid: (B,) how many of this call's T tokens are real for
    each slot (0 = slot idle this step).

    One function covers both serving phases: decode is T=1/t_valid=1,
    chunked prefill is T=chunk with t_valid up to chunk — slots may mix
    phases freely within a call.  K/V are scattered through the page
    table *before* attention reads them, so in-chunk causal
    self-attention falls out of the position mask.  The path is
    ``paged_attention_path``'s.  Returns (out (B,T,D), k_store,
    v_store).
    """
    from .sharding import constrain
    B, T, _ = x.shape
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions)
    k_store = paged_scatter(k_store, k, page_table, lengths, t_valid, layer)
    v_store = paged_scatter(v_store, v, page_table, lengths, t_valid, layer)
    # under a mesh: the pool stays block-replicated / head_dim-sharded
    # through the scatter, so XLA never resorts to resharding the whole
    # pool around the donated update (no-op without a mesh context)
    lead = (None,) * (k_store.ndim - 1)
    k_store = constrain(k_store, *lead, "model")
    v_store = constrain(v_store, *lead, "model")
    if paged_attention_path(k_store.dtype) == "pallas":
        from ..kernels.decode_attention.ops import paged_attention_bthd
        out = paged_attention_bthd(q, k_store, v_store, page_table, lengths,
                                   t_valid, layer=layer)
    else:
        hd = q.shape[-1]
        out = paged_attention(
            q, paged_gather(_unfold(k_store, hd), page_table, layer),
            paged_gather(_unfold(v_store, hd), page_table, layer), positions)
    # attention runs in the pool's precision; the residual stream stays
    # in the compute dtype (a bf16 model may keep an f32 pool)
    out = out.astype(x.dtype)
    return out.reshape(B, T, -1) @ p["wo"], k_store, v_store


# ---------------------------------------------------------------------------
# int8 block-quantized paged KV
# ---------------------------------------------------------------------------

QUANT_EPS = 1e-8


def quantize_kv(x):
    """Symmetric per-row-per-head int8 quantization over head_dim.

    x: (..., hd) float -> (q (..., hd) int8, scale (...) float32) with
    ``dequant = q.astype(f32) * scale[..., None]``.  The scale is
    amax/127 over the head_dim axis only, so every (token, head) row
    carries its own scale: a row written once is never requantized when
    later tokens land in the same block (incremental prefill/decode
    appends stay exact per-row, which a whole-block scale could not
    guarantee).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of ``quantize_kv``: (..., hd) int8 × (...) f32 -> f32."""
    return q.astype(jnp.float32) * scale[..., None]


def gqa_paged_step_quant(p, cfg: ModelConfig, x, k_store, v_store,
                         k_scale, v_scale, page_table, lengths, t_valid,
                         layer=None):
    """Int8 variant of ``gqa_paged_step`` (always the jnp path).

    k_store/v_store: (num_blocks, KV, rows, lanes) int8 pools;
    k_scale/v_scale: (num_blocks, KV, block_size) float32 per-row scale
    pools that ride the same page-table indirection; each with a leading
    layer axis when ``layer`` is given.  New K/V rows are
    quantized post-RoPE and scattered alongside their scales; the gather
    dequantizes back to f32 before the (unchanged) ``paged_attention``
    core, so the only numeric difference from the f32 path is the int8
    round-trip on cached keys/values.  Returns
    (out, k_store, v_store, k_scale, v_scale).
    """
    from .sharding import constrain
    B, T, _ = x.shape
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    k_store = paged_scatter(k_store, kq, page_table, lengths, t_valid, layer)
    v_store = paged_scatter(v_store, vq, page_table, lengths, t_valid, layer)
    # scale rows (B,T,KV) take the same page-wise scatter: the
    # (nb,KV,bs) scale pool is a storage with no head_dim axis
    k_scale = paged_scatter(k_scale, ks, page_table, lengths, t_valid, layer)
    v_scale = paged_scatter(v_scale, vs, page_table, lengths, t_valid, layer)
    lead = (None,) * (k_store.ndim - 1)
    k_store = constrain(k_store, *lead, "model")
    v_store = constrain(v_store, *lead, "model")
    k_scale = constrain(k_scale, *lead)
    v_scale = constrain(v_scale, *lead)
    hd = q.shape[-1]
    k_gath = dequantize_kv(
        paged_gather(_unfold(k_store, hd), page_table, layer),
        paged_gather(k_scale, page_table, layer))
    v_gath = dequantize_kv(
        paged_gather(_unfold(v_store, hd), page_table, layer),
        paged_gather(v_scale, page_table, layer))
    out = paged_attention(q, k_gath, v_gath, positions).astype(x.dtype)
    return (out.reshape(B, T, -1) @ p["wo"],
            k_store, v_store, k_scale, v_scale)


# ---------------------------------------------------------------------------
# full attention layers (projection + rope + core) — GQA
# ---------------------------------------------------------------------------

def _project_qkv(p, cfg: ModelConfig, x):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return (q.reshape(B, S, h, hd), k.reshape(B, S, kv, hd),
            v.reshape(B, S, kv, hd))


def _rope_qk(cfg: ModelConfig, q, k, positions):
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k


def gqa_forward(p, cfg: ModelConfig, x, positions, *, causal: bool = True,
                impl: str = "naive", chunk: int = 1024, remat: bool = False,
                unroll: bool = False, acc_bf16: bool = False,
                probs_bf16: bool = False):
    """Training/prefill forward.  positions: (B,S) or (3,B,S) for mrope."""
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions)
    if impl == "chunked":
        out = chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                sliding_window=cfg.sliding_window, remat=remat,
                                unroll=unroll, acc_bf16=acc_bf16,
                                probs_bf16=probs_bf16)
    else:
        out = naive_attention(q, k, v, causal=causal,
                              sliding_window=cfg.sliding_window)
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ p["wo"]


def gqa_prefill(p, cfg: ModelConfig, x, positions, *, impl: str = "chunked",
                chunk: int = 1024, unroll: bool = False,
                probs_bf16: bool = False):
    """Prefill: returns (out, (k, v)) for cache seeding."""
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions)
    if impl == "chunked":
        out = chunked_attention(q, k, v, causal=True, chunk=chunk,
                                sliding_window=cfg.sliding_window,
                                unroll=unroll, probs_bf16=probs_bf16)
    else:
        out = naive_attention(q, k, v, causal=True,
                              sliding_window=cfg.sliding_window)
    B, S = x.shape[:2]
    return out.reshape(B, S, -1) @ p["wo"], (k, v)


def gqa_decode(p, cfg: ModelConfig, x, k_cache, v_cache, pos):
    """Decode one token.  x: (B,1,D); pos: scalar position of this token.

    Returns (out, k_cache, v_cache) with the new token inserted.
    """
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions)
    window = cfg.sliding_window
    cap = k_cache.shape[1]
    idx = jnp.mod(pos, cap) if window else pos
    k_cache = _dynamic_token_update(k_cache, k, idx)
    v_cache = _dynamic_token_update(v_cache, v, idx)
    out = decode_attention(q, k_cache, v_cache, pos,
                           window=window).astype(x.dtype)
    B = x.shape[0]
    return out.reshape(B, 1, -1) @ p["wo"], k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3) — cache holds (c_kv, k_rope): the latent compression
# ---------------------------------------------------------------------------

def _mla_qkv(p, cfg: ModelConfig, x, positions):
    m = cfg.mla
    h = cfg.n_heads
    B, S, _ = x.shape
    from .common import rmsnorm
    cq = rmsnorm(p["q_norm"], x @ p["wdq"])
    q = (cq @ p["wuq"]).reshape(B, S, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = rmsnorm(p["kv_norm"], x @ p["wdkv"])          # (B,S,rank)
    k_rope = apply_rope((x @ p["wkr"]).reshape(B, S, 1, m.qk_rope_head_dim),
                        positions, cfg.rope_theta)        # shared single head
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(p, cfg: ModelConfig, c_kv):
    m = cfg.mla
    B, T = c_kv.shape[:2]
    h = cfg.n_heads
    k_nope = (c_kv @ p["wuk"]).reshape(B, T, h, m.qk_nope_head_dim)
    v = (c_kv @ p["wuv"]).reshape(B, T, h, m.v_head_dim)
    return k_nope, v


def mla_forward(p, cfg: ModelConfig, x, positions, *, causal: bool = True,
                impl: str = "naive", chunk: int = 1024, remat: bool = False,
                unroll: bool = False, acc_bf16: bool = False,
                probs_bf16: bool = False):
    m = cfg.mla
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    k_nope, v = _mla_expand_kv(p, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, k_nope.shape[:3] + (m.qk_rope_head_dim,))],
                        axis=-1)
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if impl == "chunked":
        out = chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                scale=scale, remat=remat, unroll=unroll,
                                acc_bf16=acc_bf16, probs_bf16=probs_bf16)
    else:
        out = naive_attention(q, k, v, causal=causal, scale=scale)
    return out.reshape(B, S, -1) @ p["wo"]


def mla_prefill(p, cfg: ModelConfig, x, positions, *, impl: str = "chunked",
                chunk: int = 1024, unroll: bool = False,
                probs_bf16: bool = False):
    """Returns (out, (c_kv, k_rope)) — the latent cache (the MLA memory win)."""
    m = cfg.mla
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    k_nope, v = _mla_expand_kv(p, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, k_nope.shape[:3] + (m.qk_rope_head_dim,))],
                        axis=-1)
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if impl == "chunked":
        out = chunked_attention(q, k, v, causal=True, chunk=chunk, scale=scale,
                                unroll=unroll, probs_bf16=probs_bf16)
    else:
        out = naive_attention(q, k, v, causal=True, scale=scale)
    return out.reshape(B, S, -1) @ p["wo"], (c_kv, k_rope.reshape(B, S, m.qk_rope_head_dim))


def mla_decode(p, cfg: ModelConfig, x, c_cache, kr_cache, pos,
               absorb: bool = False):
    """Decode with latent cache.  c_cache: (B,C,rank); kr_cache: (B,C,rd).

    ``absorb=True`` folds W_uk into the query (q_nope @ W_uk^T per head)
    so attention runs directly in the latent space — the beyond-paper
    decode optimization; ``False`` re-expands K from the cache (naive).
    """
    m = cfg.mla
    h = cfg.n_heads
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    c_cache = jax.lax.dynamic_update_slice(
        c_cache, c_kv.astype(c_cache.dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        kr_cache, k_rope.reshape(B, 1, m.qk_rope_head_dim).astype(kr_cache.dtype),
        (0, pos, 0))
    C = c_cache.shape[1]
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    slot = jnp.arange(C)[None, :]
    valid = slot <= pos
    if absorb:
        # q_lat: (B,1,h,rank) = q_nope @ W_uk (absorbed)
        wuk = p["wuk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wuk)
        s_lat = jnp.einsum("bshr,btr->bhst", q_lat, c_cache)
        s_rope = jnp.einsum("bshd,btd->bhst", q_rope, kr_cache)
        scores = ((s_lat + s_rope) * scale).astype(jnp.float32)
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_cache.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, c_cache)  # (B,1,h,rank)
        wuv = p["wuv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
        out = jnp.einsum("bshr,rhd->bshd", o_lat, wuv)
    else:
        k_nope, v = _mla_expand_kv(p, cfg, c_cache)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_cache[:, :, None, :],
                                      k_nope.shape[:3] + (m.qk_rope_head_dim,))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        scores = (jnp.einsum("bshd,bthd->bhst", q * scale, k)).astype(jnp.float32)
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(B, 1, -1) @ p["wo"], c_cache, kr_cache
