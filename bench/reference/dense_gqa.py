"""Plain reference of a dense decoder with grouped-query attention, in
float32 at full matmul precision: RMSNorm, rotary embeddings (as
interleaved pairs: the layout of Meta's own code, which equals Hugging
Face's rotate-half layout under a fixed permutation of the q/k
columns), causal attention with K/V heads shared by groups of query
heads, a SwiGLU feed-forward block, a final RMSNorm and an output head:
the embedding's transpose where the configuration ties them
(``tie_word_embeddings``), its own matrix where it does not.

It reads the served weights by their names in the parameter tree (the
arrays the benchmark made from the seed) and imports nothing of the
program.  The layer loop is a scan, one layer's weights at a time, so
it fits beside the weights on one chip.

``precision="fp8"`` is the control: every matmul takes its inputs
rounded to float8 e4m3 (weights scaled per matrix, activations per
row), the next precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _spec(config: Dict[str, Any]) -> Dict[str, Any]:
    c = config["config"]
    h = int(c["num_attention_heads"])
    return dict(
        heads=h, kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim", int(c["hidden_size"]) // h)),
        eps=float(c["rms_norm_eps"]),
        theta=float(c.get("rope_theta", 10000.0)),
        tied=bool(c.get("tie_word_embeddings", False)))


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, n, hd); rotate hd as pairs (0, 1), (2, 3), ..."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _layer(x, p, sp, fp8):
    S = x.shape[0]
    H, KV, hd = sp["heads"], sp["kv_heads"], sp["head_dim"]
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(x, p["norm1"]["scale"], sp["eps"])
    q, k, v = (_mm(h, a["wq"], fp8), _mm(h, a["wk"], fp8),
               _mm(h, a["wv"], fp8))
    q = _rope(q.reshape(S, H, hd), pos, sp["theta"])
    k = _rope(k.reshape(S, KV, hd), pos, sp["theta"])
    v = v.reshape(S, KV, hd)
    q = q.reshape(S, KV, H // KV, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) / np.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(S, H * hd)
    x = x + _mm(o, a["wo"], fp8)
    m = p["mlp"]
    h = _rms(x, p["norm2"]["scale"], sp["eps"])
    x = x + _mm(jax.nn.silu(_mm(h, m["w_gate"], fp8)) * _mm(h, m["w_up"], fp8),
                m["w_down"], fp8)
    return x


@functools.partial(jax.jit, static_argnames=("sp", "n_new", "fp8"))
def _forward(params, tokens, start, *, sp, n_new, fp8):
    """Full forward over ``tokens`` (S,); the logits of the ``n_new``
    positions from ``start``."""
    spd = dict(sp)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(x, p, spd, fp8), None
    x, _ = jax.lax.scan(body, x, params["blocks"]["s0"])
    x = jax.lax.dynamic_slice_in_dim(x, start, n_new, axis=0)
    h = _rms(x, params["final_norm"]["scale"], spd["eps"])
    head = params["embed"].T if spd["tied"] else params["lm_head"]
    return _mm(h, head, fp8)


def logits(params, config, tokens, start: int, n_new: int,
           precision: str = "f32"):
    """Logits (n_new, vocab) float32 at positions start .. start+n_new-1
    of ``tokens`` (int32 (S,))."""
    sp = tuple(sorted(_spec(config).items()))
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    jnp.int32(start), sp=sp, n_new=int(n_new),
                    fp8=precision == "fp8")
