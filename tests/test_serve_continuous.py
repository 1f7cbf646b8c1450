"""Continuous batching in ServeEngine: mid-decode admission, eviction
on eos_id, request-order results, and cache-splice integrity.

Uses a deterministic toy model whose generation state lives ONLY in the
KV-cache analogue: prefill stores ``cur = (sum(prompt) % vocab)`` in the
cache and every decode step emits ``cur + 1`` — the fed-back token is
ignored.  Any corruption of an in-flight slot's cache by a mid-decode
join therefore derails that sequence visibly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import ServeEngine

VOCAB = 97


class ToyModel:
    """prefill/decode_step-compatible counter model (cache-driven)."""

    def prefill(self, params, tokens, capacity, extra_embeds=None,
                cache_dtype=jnp.float32):
        base = jnp.sum(tokens, axis=1).astype(jnp.int32) % VOCAB  # (B,)
        first = (base + 1) % VOCAB
        cache = {"cur": first,
                 "kv": jnp.zeros((tokens.shape[0], capacity), cache_dtype)}
        return jax.nn.one_hot(first, VOCAB) * 100.0, cache

    def decode_step(self, params, cache, token, pos):
        nxt = (cache["cur"] + 1) % VOCAB
        logits = jax.nn.one_hot(nxt, VOCAB) * 100.0
        kv = cache["kv"].at[:, pos].set(1.0)
        return logits, {"cur": nxt, "kv": kv}


def _expected(prompt, max_new, eos_id=None):
    base = int(np.sum(prompt)) % VOCAB
    toks = [(base + 1 + k) % VOCAB for k in range(max_new)]
    if eos_id is not None and eos_id in toks:
        toks = toks[: toks.index(eos_id) + 1]
    return toks


def _engine(**kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("capacity", 64)
    kw.setdefault("max_new_tokens", 6)
    return ServeEngine(ToyModel(), params={}, **kw)


def test_serve_returns_results_in_request_order():
    eng = _engine(batch_size=2, max_new_tokens=4)
    prompts = [np.arange(1, n + 2, dtype=np.int32) for n in range(5)]
    res = eng.serve(prompts)
    assert [r.request_id for r in res] == [0, 1, 2, 3, 4]
    for p, r in zip(prompts, res):
        assert list(r.tokens) == _expected(p, 4)
    assert eng.n_evictions == 5
    assert eng.n_prefills >= 2  # more than one wave for 5 reqs on 2 slots


def test_eviction_on_eos_id():
    # prompt sums to eos_id - 2 -> generates eos after 2 tokens
    eos = 10
    prompt = np.asarray([3, 5], np.int32)          # base 8 -> 9, 10(eos)
    long_prompt = np.asarray([20, 21], np.int32)   # base 41 -> never hits 10
    eng = _engine(batch_size=2, max_new_tokens=6, eos_id=eos)
    res = eng.serve([prompt, long_prompt])
    assert list(res[0].tokens) == [9, 10]          # stopped at eos, not max_new
    assert len(res[1].tokens) == 6                 # ran to max_new
    assert eng.n_evictions == 2


def test_late_request_joins_mid_decode():
    eos = 7
    eng = _engine(batch_size=2, max_new_tokens=8, eos_id=eos)
    a = np.asarray([2, 3], np.int32)      # base 5 -> 6, 7(eos): frees its slot
    b = np.asarray([30, 31], np.int32)    # base 61: runs all 8 steps
    eng.submit(a)
    eng.submit(b)
    finished = []
    for _ in range(3):                    # a finishes within 3 steps
        finished += eng.step()
    assert any(r.request_id == 0 for r in finished)
    assert eng.n_active == 1              # b still decoding, one slot free
    late = np.asarray([4, 4], np.int32)   # short prompt: fits current pos
    eng.submit(late)
    while eng.has_work:
        finished += eng.step()
    assert eng.n_joins == 1               # late request joined mid-decode
    by_id = {r.request_id: list(r.tokens) for r in finished}
    assert by_id[0] == [6, 7]
    assert by_id[1] == _expected(b, 8, eos)
    assert by_id[2] == _expected(late, 8, eos)  # joined slot decodes correctly


def test_join_does_not_corrupt_inflight_sequence():
    """The cache splice must leave other slots' state untouched."""
    eng = _engine(batch_size=2, max_new_tokens=10, eos_id=3)
    a = np.asarray([1, 1], np.int32)      # base 2 -> 3(eos) immediately
    b = np.asarray([50, 0, 0, 0], np.int32)  # base 50, long prompt, no eos
    eng.submit(b)
    eng.submit(a)
    results = []
    while eng.has_work:
        results += eng.step()
        if eng.n_active == 1 and eng._next_rid == 2:
            eng.submit(np.asarray([5], np.int32))  # join while b in flight
    by_id = {r.request_id: list(r.tokens) for r in results}
    assert eng.n_joins == 1
    # b's generation is the uninterrupted counter sequence despite the join
    assert by_id[0] == _expected(b, 10, 3)
    assert by_id[2] == _expected(np.asarray([5]), 10, 3)


def test_long_prompt_defers_until_position_catches_up():
    eng = _engine(batch_size=2, max_new_tokens=12)
    short = np.asarray([1, 1], np.int32)
    eng.submit(short)
    results = eng.step()                   # prefill wave: pos = 2
    long = np.arange(1, 7, dtype=np.int32)  # len 6 > pos: must wait
    eng.submit(long)
    while eng.has_work:
        results += eng.step()
    by_id = {r.request_id: list(r.tokens) for r in results}
    assert by_id[1] == _expected(long, 12)
    assert len(by_id) == 2


def test_submit_rejects_prompt_longer_than_capacity():
    eng = _engine(capacity=8)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(np.arange(1, 11, dtype=np.int32))  # len 10 > capacity 8


def test_pipeline_filter_adapter_row_order():
    eng = _engine(batch_size=2, max_new_tokens=3)
    fn = eng.as_pipeline_filter()
    prompts = np.stack([np.asarray([i + 1, i + 2], np.int32) for i in range(4)])
    out = fn(prompts)
    assert out.shape == (4, 3)
    for i in range(4):
        assert list(out[i]) == _expected(prompts[i], 3)


def test_pipeline_filter_skips_bucket_pad_rows():
    """A TensorFilter pads a 3-frame batch up to its bucket of 4 with a
    meta-less zero row; the meta-aware engine filter must serve only
    the 3 real requests."""
    from repro.core.elements.filter import TensorFilter
    eng = _engine(batch_size=4, max_new_tokens=3)
    filt = TensorFilter("llm", framework="python", max_batch=4,
                        pass_meta=True, fn=eng.as_pipeline_filter(use_meta=True))
    prompts = np.stack([np.asarray([i + 1, i + 2], np.int32) for i in range(3)])
    metas = [{"request": i} for i in range(3)]
    (out,) = filt.invoke_batched([prompts], 3, metas=metas)
    assert eng.n_requests == 3
    assert out.shape == (3, 3)
    for i in range(3):
        assert list(out[i]) == _expected(prompts[i], 3)
        assert metas[i]["status"] == "ok"
