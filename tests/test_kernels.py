"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(0)


# -- transform ----------------------------------------------------------------

@pytest.mark.parametrize("shape", [(5,), (7, 13), (3, 33, 5), (2, 8, 128)])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_transform_kernel(shape, dtype):
    from repro.kernels.transform import ops
    from repro.kernels.transform.ref import fused_transform_ref
    x = (rng.random(shape) * 200).astype(dtype)
    y = ops.fused_transform(x, scale=1 / 255.0, bias=-0.4, lo=-0.3, hi=0.3,
                            out_dtype=jnp.float32)
    yr = fused_transform_ref(jnp.asarray(x), 1 / 255.0, -0.4, -0.3, 0.3,
                             jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)


# -- moe gating ------------------------------------------------------------------

@pytest.mark.parametrize("T,E,k", [(7, 8, 2), (64, 16, 4), (130, 256, 8),
                                   (520, 16, 1)])
def test_gating_kernel(T, E, k):
    from repro.kernels.moe_gating import ops
    from repro.kernels.moe_gating.ref import topk_ref
    s = rng.standard_normal((T, E)).astype(np.float32)
    v, i = ops.topk(jnp.asarray(s), k)
    vr, ir = topk_ref(jnp.asarray(s), k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-6)
    assert np.array_equal(np.asarray(i), np.asarray(ir))


def test_gating_batched_shape():
    from repro.kernels.moe_gating import ops
    s = rng.standard_normal((2, 9, 16)).astype(np.float32)
    v, i = ops.topk(jnp.asarray(s), 3)
    assert v.shape == (2, 9, 3) and i.shape == (2, 9, 3)


# -- flash attention ---------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd,bq,bk", [
    (1, 2, 2, 32, 16, 16, 16),      # MHA
    (2, 4, 2, 64, 32, 32, 32),      # GQA
    (1, 8, 1, 48, 64, 16, 16),      # MQA, non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(B, H, KV, S, hd, bq, bk, dtype):
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention.ref import attention_ref
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), dtype)
    o = ops.flash_attention_bshd(q, k, v, causal=True, block_q=bq, block_k=bk)
    orf = attention_ref(jnp.moveaxis(q, 2, 1).astype(jnp.float32),
                        jnp.moveaxis(k, 2, 1).astype(jnp.float32),
                        jnp.moveaxis(v, 2, 1).astype(jnp.float32), causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(o, 2, 1), np.float32),
                               np.asarray(orf), atol=tol, rtol=tol)


def test_flash_attention_sliding_window():
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention.ref import attention_ref
    B, S, H, hd, w = 1, 64, 2, 16, 24
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    o = ops.flash_attention_bshd(q, k, v, causal=True, sliding_window=w,
                                 block_q=16, block_k=16)
    orf = attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                        jnp.moveaxis(v, 2, 1), causal=True, sliding_window=w)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(o, 2, 1)),
                               np.asarray(orf), atol=1e-5, rtol=1e-5)


# -- decode attention -----------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,C,hd,length", [
    (2, 4, 2, 96, 32, 70), (1, 8, 8, 64, 64, 64), (3, 6, 2, 40, 16, 1),
])
def test_decode_attention_kernel(B, H, KV, C, hd, length):
    from repro.kernels.decode_attention import ops
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, C, KV, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, C, KV, hd))
    o = ops.decode_attention_bhd(q, kc, vc, length, block_k=32)
    orf = decode_attention_ref(q[:, 0], jnp.moveaxis(kc, 2, 1),
                               jnp.moveaxis(vc, 2, 1), length)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(orf),
                               atol=1e-5, rtol=1e-5)


# -- paged attention over live pages ---------------------------------------------

def _paged_case(T, G, hd, bs, lengths, t_valid, *, KV=2, P=4, seed=0):
    """Random q and pools in the engine's folded layout, each slot on
    its own blocks, with the jnp path's answer for the real rows."""
    from repro.models.attention import (paged_attention, paged_gather,
                                        paged_page_shape)
    r = np.random.default_rng(seed)
    B, H = len(lengths), KV * G
    nb = B * P + 3
    rows, lanes = paged_page_shape(bs, hd)
    q = jnp.asarray(r.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((nb, KV, rows, lanes)), jnp.float32)
    v = jnp.asarray(r.standard_normal((nb, KV, rows, lanes)), jnp.float32)
    pt = jnp.asarray(r.permutation(nb)[:B * P].reshape(B, P), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    t_valid = jnp.asarray(t_valid, jnp.int32)
    unfold = lambda a: a.reshape(nb, KV, bs, hd)
    pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    ref = paged_attention(q, paged_gather(unfold(k), pt),
                          paged_gather(unfold(v), pt), pos)
    return q, k, v, pt, lengths, t_valid, ref


@pytest.mark.parametrize("T,bs,lengths,t_valid,poison", [
    (1, 16, [0, 15, 16, 33], [1, 1, 1, 1], False),   # burst, on/off pages
    (1, 16, [5, 47, 32, 0], [1, 0, 1, 1], False),    # an idle slot
    (4, 16, [0, 13, 29, 60], [4, 3, 0, 4], False),   # ragged chunks
    (4, 8, [16, 6, 0, 23], [2, 4, 1, 0], False),     # two pages per row
    (4, 16, [12, 28, 0, 44], [4, 4, 2, 1], True),    # dead pages poisoned
    (1, 16, [3, 16, 31, 0], [1, 1, 1, 0], True),
])
def test_paged_attention_kernel(T, bs, lengths, t_valid, poison):
    """The Pallas kernel (interpret mode) equals the jnp path on every
    real query row, writes zeros for idle slots, and reads no page
    past a slot's live extent: with every block that no slot's extent
    reaches filled with NaN, the output stays finite and unchanged."""
    from repro.kernels.decode_attention.ops import paged_attention_bthd
    G, hd = 3, 64
    q, k, v, pt, lengths, t_valid, ref = _paged_case(T, G, hd, bs, lengths,
                                                     t_valid)
    if poison:
        live = np.zeros(k.shape[0], bool)
        for b in range(len(lengths)):
            n = -(-int(lengths[b] + t_valid[b]) // bs) if t_valid[b] else 0
            live[np.asarray(pt[b, :n])] = True
        dead = jnp.asarray(~live)[:, None, None, None]
        k = jnp.where(dead, jnp.nan, k)
        v = jnp.where(dead, jnp.nan, v)
    out = np.asarray(paged_attention_bthd(q, k, v, pt, lengths, t_valid))
    assert np.isfinite(out).all()
    for b in range(len(lengths)):
        n = int(t_valid[b])
        np.testing.assert_allclose(out[b, :n], np.asarray(ref[b, :n]),
                                   atol=1e-5, rtol=1e-5)
        if n == 0:
            assert not out[b].any()


def test_paged_attention_path(monkeypatch):
    """The path is chosen from what the trace can observe: the jnp path
    on CPU; the kernel on a TPU backend with no mesh and a float pool;
    the jnp path again for an int8 pool or under an active mesh."""
    from jax.sharding import Mesh

    from repro.models import attention as A
    assert A.paged_attention_path(jnp.bfloat16) == "jnp"
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert A.paged_attention_path(jnp.bfloat16) == "pallas"
    assert A.paged_attention_path(jnp.float32) == "pallas"
    assert A.paged_attention_path(jnp.int8) == "jnp"
    with Mesh(np.array(jax.devices()[:1]), ("model",)):
        assert A.paged_attention_path(jnp.bfloat16) == "jnp"


# -- int8 paged decode attention ----------------------------------------------

@pytest.mark.parametrize("B,H,KV,hd,nb,bs,P", [
    (3, 4, 2, 16, 12, 8, 3), (2, 8, 8, 32, 10, 16, 2),
])
def test_paged_decode_attention_quant_kernel(B, H, KV, hd, nb, bs, P):
    """Int8 kernel == dequantize-then-attend oracle (exact), and the
    int8 round-trip vs the float kernel stays within drift tolerance."""
    from repro.kernels.decode_attention import ops
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_quant_ref)
    from repro.models.attention import paged_page_shape, quantize_kv
    kf = jnp.asarray(rng.standard_normal((nb, KV, bs, hd)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((nb, KV, bs, hd)), jnp.float32)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    assert kq.dtype == jnp.int8 and ks.shape == (nb, KV, bs)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    pt = jnp.asarray(np.stack([rng.permutation(nb)[:P] for _ in range(B)]),
                     jnp.int32)
    lengths = jnp.asarray(rng.integers(1, P * bs + 1, B), jnp.int32)
    o = ops.paged_decode_attention_quant_bhd(q, kq, vq, ks, vs, pt, lengths)
    orf = paged_decode_attention_quant_ref(q[:, 0], kq, vq, ks, vs, pt,
                                           lengths)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(orf),
                               atol=1e-5, rtol=1e-5)
    page = (nb, KV) + paged_page_shape(bs, hd)
    of = ops.paged_attention_bthd(q, kf.reshape(page), vf.reshape(page), pt,
                                  lengths - 1, jnp.ones_like(lengths))
    assert float(jnp.max(jnp.abs(o - of))) < 5e-2   # int8 drift, not exact


# -- interpret autodetect -----------------------------------------------------

def test_interpret_defaults_to_backend_autodetect():
    """Every kernels/*/ops.py entry point defaults interpret=None and
    resolves it through default_interpret(): CPU hosts autodetect to
    interpret mode (compiled Pallas silently miscompiles or crashes on
    CPU), explicit overrides pass through untouched.  The kernel.py
    entry points below them take ``interpret`` as a required keyword,
    so a direct call can never run interpreted on a chip unasked."""
    import inspect

    from repro.kernels import default_interpret
    from repro.kernels.decode_attention import kernel as dk
    from repro.kernels.decode_attention.ops import (
        decode_attention_bhd, paged_attention_bthd,
        paged_decode_attention_quant_bhd)
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ops import flash_attention_bshd
    from repro.kernels.moe_gating.kernel import gating_topk
    from repro.kernels.moe_gating.ops import topk
    from repro.kernels.ssm_scan.kernel import selective_scan_kernel
    from repro.kernels.ssm_scan.ops import selective_scan
    from repro.kernels.transform.kernel import fused_transform_2d
    from repro.kernels.transform.ops import fused_transform
    for fn in (decode_attention_bhd, paged_attention_bthd,
               paged_decode_attention_quant_bhd, flash_attention_bshd,
               topk, selective_scan, fused_transform):
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn.__name__
    for fn in (dk.decode_attention, dk.paged_attention,
               dk.paged_decode_attention_quant, flash_attention,
               gating_topk, selective_scan_kernel, fused_transform_2d):
        param = inspect.signature(fn).parameters["interpret"]
        assert param.kind is inspect.Parameter.KEYWORD_ONLY, fn.__name__
        assert param.default is inspect.Parameter.empty, fn.__name__
    assert default_interpret() == (jax.default_backend() == "cpu")
    assert default_interpret(True) is True
    assert default_interpret(False) is False


# -- ssm scan -----------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,N,bd,ct", [
    (1, 16, 32, 4, 16, 8), (2, 48, 96, 8, 32, 16), (1, 100, 64, 16, 64, 32),
    (1, 300, 32, 4, 32, 256),   # two 128-lane column slices per chunk
])
def test_ssm_scan_kernel(B, S, di, N, bd, ct):
    from repro.kernels.ssm_scan import ops
    from repro.kernels.ssm_scan.ref import selective_scan_ref
    dt = jnp.asarray(rng.random((B, S, di)).astype(np.float32) * 0.1)
    xs = jnp.asarray(rng.standard_normal((B, S, di)).astype(np.float32))
    Bc = jnp.asarray(rng.standard_normal((B, S, N)).astype(np.float32))
    Cc = jnp.asarray(rng.standard_normal((B, S, N)).astype(np.float32))
    A = -jnp.asarray(rng.random((di, N)).astype(np.float32))
    D = jnp.ones((di,), jnp.float32)
    y, h = ops.selective_scan(dt, Bc, Cc, xs, A, D, block_d=bd, chunk_t=ct)
    yr, hr = selective_scan_ref(dt, Bc, Cc, xs, A, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-5,
                               rtol=2e-4)


def test_ssm_scan_matches_model_path():
    """Kernel == the model's pure-jnp selective_scan."""
    from repro.kernels.ssm_scan import ops
    from repro.models.mamba import selective_scan
    B, S, di, N = 2, 32, 64, 8
    dt = jnp.asarray(rng.random((B, S, di)).astype(np.float32) * 0.1)
    xs = jnp.asarray(rng.standard_normal((B, S, di)).astype(np.float32))
    Bc = jnp.asarray(rng.standard_normal((B, S, N)).astype(np.float32))
    Cc = jnp.asarray(rng.standard_normal((B, S, N)).astype(np.float32))
    A = -jnp.asarray(rng.random((di, N)).astype(np.float32))
    D = jnp.ones((di,), jnp.float32)
    y1, h1 = selective_scan(dt, Bc, Cc, xs, A, D)
    y2, h2 = ops.selective_scan(dt, Bc, Cc, xs, A, D, block_d=32, chunk_t=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5,
                               rtol=2e-4)
