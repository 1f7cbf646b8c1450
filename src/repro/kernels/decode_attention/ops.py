"""Public ops: decode and paged attention in model-native layout."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import default_interpret
from .kernel import (decode_attention, paged_attention,
                     paged_decode_attention_quant)


def decode_attention_bhd(q, k_cache, v_cache, length, *, block_k: int = 512,
                         interpret: Optional[bool] = None):
    """q: (B,1,H,hd); caches: (B,C,KV,hd) -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    C = k_cache.shape[1]
    bk = min(block_k, C)
    pad = (-C) % bk
    kt = jnp.moveaxis(k_cache, 2, 1)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad:  # padded slots are masked by the length check (length <= C)
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o = decode_attention(q[:, 0], kt, vt, length, block_k=bk,
                         interpret=default_interpret(interpret))
    return o[:, None]


def paged_attention_bthd(q, k_pages, v_pages, page_table, lengths, t_valid,
                         *, layer=None, interpret: Optional[bool] = None):
    """Paged attention in the serving engine's layout, read in place.

    q: (B,T,H,hd); k_pages/v_pages: (num_blocks, KV, block_size, hd) —
    the ``ServeEngine`` paged-cache leaf layout — or the (L, ...) stack
    of every layer's pools with ``layer`` the one read; page_table:
    (B,P); lengths: (B,) positions cached before this step; t_valid:
    (B,) real tokens of this step.  Returns (B,T,H,hd).
    """
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return paged_attention(q, k_pages, v_pages, page_table, lengths, t_valid,
                           layer, interpret=default_interpret(interpret))


def paged_decode_attention_quant_bhd(q, k_pages, v_pages, k_scale, v_scale,
                                     page_table, lengths, *,
                                     interpret: Optional[bool] = None):
    """Int8 paged decode attention in the serving engine's layout.

    q: (B,1,H,hd) float; k_pages/v_pages: (num_blocks, KV, block_size,
    hd) int8 — the ``kv_dtype="int8"`` paged-cache leaf layout;
    k_scale/v_scale: (num_blocks, KV, block_size) float32 per-row
    scales; page_table: (B,P); lengths: (B,).  Returns (B,1,H,hd).
    """
    o = paged_decode_attention_quant(q[:, 0], k_pages, v_pages, k_scale,
                                     v_scale, page_table, lengths,
                                     interpret=default_interpret(interpret))
    return o[:, None]
