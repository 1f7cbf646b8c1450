"""Parameter/activation PartitionSpec rules (megatron TP + FSDP + EP).

Axes:
  * "model" — tensor parallel: attention heads, FFN hidden, vocab, experts
  * "data" (+ "pod" when multi-pod) — batch / FSDP shard of the non-TP dim

Rules are matched against the flattened param path; scan-stacked leaves
(under ``blocks/``) get a leading ``None`` for the period dim.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# (regex on path, spec WITHOUT the stacked-leading-None)
# dp = FSDP axis name tuple; tp = "model"
def _rules(dp):
    return [
        # embeddings / lm head: vocab on tp, d_model on dp (FSDP)
        (r"embed$", P("model", dp)),
        (r"lm_head$", P(dp, "model")),
        (r"pos_emb$", P(None, dp)),
        # attention (GQA)
        (r"attn/w[qkv]$", P(dp, "model")),
        (r"attn/wo$", P("model", dp)),
        (r"attn/b[qkv]$", P("model")),
        # MLA
        (r"attn/wdq$", P(dp, None)),
        (r"attn/wuq$", P(None, "model")),
        (r"attn/wdkv$", P(dp, None)),
        (r"attn/wkr$", P(dp, None)),
        (r"attn/wuk$", P(None, "model")),
        (r"attn/wuv$", P(None, "model")),
        # dense MLP
        (r"mlp/w_gate$", P(dp, "model")),
        (r"mlp/w_up$", P(dp, "model")),
        (r"mlp/w_down$", P("model", dp)),
        # MoE (expert parallel over tp; FSDP over d inside each expert)
        (r"moe/router$", P(dp, None)),
        (r"moe/router_bias$", P()),
        (r"moe/w_gate$", P("model", dp, None)),
        (r"moe/w_up$", P("model", dp, None)),
        (r"moe/w_down$", P("model", None, dp)),
        (r"moe/shared/w_gate$", P(dp, "model")),
        (r"moe/shared/w_up$", P(dp, "model")),
        (r"moe/shared/w_down$", P("model", dp)),
        # mamba (shard d_inner on tp)
        (r"mamba/in_proj$", P(dp, "model")),
        (r"mamba/conv_w$", P(None, "model")),
        (r"mamba/conv_b$", P("model")),
        (r"mamba/x_proj$", P("model", None)),
        (r"mamba/dt_proj$", P(None, "model")),
        (r"mamba/dt_bias$", P("model")),
        (r"mamba/A_log$", P("model", None)),
        (r"mamba/D$", P("model")),
        (r"mamba/out_proj$", P("model", dp)),
        # xlstm (shard heads / d_inner on tp)
        (r"(mlstm|slstm)/up$", P(dp, "model")),
        (r"mlstm/w[qkv]$", P("model", None)),
        (r"mlstm/w_if$", P("model", None)),
        (r"mlstm/b_if$", P()),
        (r"slstm/W$", P("model", None)),
        # slstm R: H (4) not divisible by model axis -> replicate
        (r"slstm/b$", P()),
        (r"(mlstm|slstm)/down$", P("model", dp)),
        # mtp projection
        (r"mtp/proj$", P(dp, None)),
    ]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _axis_size(axis, axis_sizes):
    if axis is None or not axis_sizes:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= axis_sizes.get(a, 1)
        return n
    return axis_sizes.get(axis, 1)


def _filter_divisible(parts, shape, axis_sizes):
    """Drop mesh axes from dims they don't divide evenly (pjit argument
    shardings require divisibility; e.g. whisper's vocab 51865)."""
    if not axis_sizes:
        return parts
    out = []
    for i, a in enumerate(parts):
        if a is not None and shape[i] % _axis_size(a, axis_sizes) != 0:
            out.append(None)
        else:
            out.append(a)
    return tuple(out)


def param_specs(params, dp=("data",), axis_sizes=None):
    """PartitionSpec pytree matching ``params``.

    If "model" is part of ``dp`` (flat data parallelism), TP placements
    collapse into the FSDP axis: any "model" entry in a rule is dropped.
    """
    flat_dp = "model" in dp
    dp_axis = dp if len(dp) > 1 else dp[0]
    rules = _rules(dp_axis)

    def spec_of(path, leaf):
        s = _path_str(path)
        stacked = "blocks/" in s or s.startswith("blocks")
        for pat, spec in rules:
            if re.search(pat, s):
                parts = tuple(spec)
                if flat_dp:
                    parts = tuple(None if a == "model" else a for a in parts)
                if stacked:
                    parts = (None,) + parts
                # pad/trim to leaf rank
                parts = parts[: leaf.ndim] + (None,) * max(leaf.ndim - len(parts), 0)
                parts = _filter_divisible(parts, leaf.shape, axis_sizes)
                return P(*parts)
        # default: replicate (norm scales, biases, small tables)
        return P(*((None,) * leaf.ndim)) if leaf.ndim else P()

    return jax.tree_util.tree_map_with_path(spec_of, params)


def param_shardings(mesh, params):
    """``NamedSharding`` pytree placing ``params`` (arrays or
    ``jax.ShapeDtypeStruct`` leaves) on ``mesh`` by ``param_specs``:
    "model" is the tensor axis, every other axis is FSDP."""
    dp = tuple(a for a in mesh.axis_names if a != "model") or ("data",)
    specs = param_specs(params, dp=dp, axis_sizes=dict(mesh.shape))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def cache_specs(cache, dp=("data",), shard_seq_when_batch1: bool = True,
                axis_sizes=None):
    """KV/state caches: batch over dp; heads over model; for batch-1
    long-context, the cache *sequence* dim shards over dp instead."""
    flat_dp = "model" in dp
    dp_axis = dp if len(dp) > 1 else dp[0]

    def spec_of(path, leaf):
        s = _path_str(path)
        stacked = "blocks/" in s or s.startswith("blocks")
        lead = (None,) if stacked else ()
        name = s.rsplit("/", 1)[-1]
        if leaf.ndim == 0:
            return P()
        batch = leaf.shape[len(lead)] if leaf.ndim > len(lead) else 1
        if name in ("k", "v"):          # (B, C, KV, hd)
            # KV head counts (2..8) don't divide the 16-way model axis;
            # shard head_dim instead (always a multiple of 16) — decode
            # scores then psum over the model axis.
            if batch == 1 and shard_seq_when_batch1:
                spec = (None, dp_axis, None, "model")
            else:
                spec = (dp_axis, None, None, "model")
        elif name in ("c", "kr"):        # MLA latents (B, C, r)
            spec = (dp_axis, None, None) if batch > 1 or not shard_seq_when_batch1 \
                else (None, dp_axis, None)
        elif name == "conv":             # (B, dc-1, di)
            spec = (dp_axis, None, "model")
        elif name == "ssm":              # (B, di, N)
            spec = (dp_axis, "model", None)
        elif name in ("C",):             # mlstm (B,H,dk,dv): H=4 too small
            spec = (dp_axis, None, None, None)
        elif name in ("n",):
            spec = (dp_axis, None) + (None,) * (leaf.ndim - len(lead) - 2)
        elif name in ("m",):
            spec = (dp_axis,) + (None,) * (leaf.ndim - len(lead) - 1)
        elif name in ("h", "cs", "ns", "ms"):  # slstm (B, di)
            spec = (dp_axis, "model")
        elif name in ("cross_k", "cross_v"):   # whisper (B, T_enc, KV, hd)
            spec = (dp_axis, None, None, "model")
        else:
            spec = (dp_axis,) + (None,) * (leaf.ndim - len(lead) - 1)
        spec = lead + spec
        if flat_dp:
            spec = tuple(None if a == "model" else a for a in spec)
        spec = spec[: leaf.ndim] + (None,) * max(leaf.ndim - len(spec), 0)
        spec = _filter_divisible(spec, leaf.shape, axis_sizes)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def paged_cache_specs(cache, axis_sizes=None):
    """PartitionSpec pytree for the serving engine's *paged* pool.

    Unlike the dense decode cache (``cache_specs``), the paged layout
    has no batch axis to data-shard: attention leaves are a single
    shared pool ``(num_blocks, KV, rows, lanes)`` addressed by
    host-side page tables, and recurrent slabs are ``(num_slots, ...)``
    addressed by host-side slot ids.  The block/slot axis must stay
    **replicated** — every device needs every page resident so a slot's
    page table works unchanged wherever its blocks landed — and tensor
    parallelism shards the *feature* dims on "model": head_dim for KV
    (KV head counts are too small to divide a large model axis),
    d_inner for mamba/xLSTM slab state.  Periodic stacked leaves (scan
    over layers) carry a leading replicated period dim.
    """

    def spec_of(path, leaf):
        s = _path_str(path)
        stacked = "blocks/" in s or s.startswith("blocks")
        lead = (None,) if stacked else ()
        name = s.rsplit("/", 1)[-1]
        rank = leaf.ndim - len(lead)
        if leaf.ndim == 0 or rank <= 0:
            return P(*((None,) * leaf.ndim))
        # (nb, KV, rows, lanes): shard the lanes, which hold head_dim
        # (several positions' head_dims where the page is folded)
        if name in ("k", "v"):
            spec = (None, None, None, "model")
        elif name in ("k_scale", "v_scale"):
            # int8 pools' per-row scales (nb, KV, bs): head_dim is
            # already reduced away, and KV head counts are too small to
            # shard — replicate (a few bytes per block)
            spec = (None, None, None)
        elif name == "conv":             # (ns, dc-1, di)
            spec = (None, None, "model")
        elif name == "ssm":              # (ns, di, d_state)
            spec = (None, "model", None)
        elif name in ("h", "cs", "ns", "ms"):  # slstm (ns, di)
            spec = (None, "model")
        else:                            # mlstm C/n/m (head dims too small)
            spec = (None,) * rank
        spec = lead + spec
        spec = spec[: leaf.ndim] + (None,) * max(leaf.ndim - len(spec), 0)
        spec = _filter_divisible(spec, leaf.shape, axis_sizes)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def _context_mesh():
    """The mesh installed by ``with mesh:`` or ``jax.set_mesh`` (None
    when no mesh is active).  jax 0.9.0's ``get_abstract_mesh()`` does
    not see the ``with mesh:`` context that the serving engine and the
    dry runs enter, so that one is read first."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if not m.empty:
        return m
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x, *spec_parts):
    """``with_sharding_constraint`` when a mesh is active, else ``x``.

    Axis names the mesh lacks are dropped, and so are axes that do not
    divide their dim evenly (the rule ``param_specs`` follows); any
    other failure is a real error and propagates."""
    mesh = _context_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    flat = []
    for p in spec_parts:
        if p is None:
            flat.append(None)
        elif isinstance(p, tuple):
            kept = tuple(q for q in p if q in names)
            flat.append(kept if kept else None)
        else:
            flat.append(p if p in names else None)
    flat = _filter_divisible(flat, x.shape, dict(mesh.shape))
    return jax.lax.with_sharding_constraint(x, P(*flat))
