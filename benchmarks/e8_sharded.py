"""E8 — tensor-parallel paged serving: decode tokens/s over mesh sizes.

Sweeps the sharded ServeEngine over ``(1, N)`` serving meshes for
N = 1 / 2 / 4 / 8 and reports steady-state paged burst-decode
throughput at each width, plus a token-identity check: every mesh size
must decode exactly the tokens the single-device engine decodes (the
sharded-serving contract — see ``tests/test_mesh_serving.py``).

The meshes are built in this process over ``jax.devices()``, and mesh
sizes beyond the device count are skipped: a chip belongs to the one
process that touched JAX first, so a child process could not reach it.
On CPU, give the caller simulated devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; they share one
socket, so that curve measures sharding *overhead* (collective cost per
token), not speedup.
"""
from __future__ import annotations

import time
from typing import List

BATCH = 8
PROMPT_LEN = 16
MAX_NEW = 40
CAPACITY = PROMPT_LEN + MAX_NEW
WINDOWS = 2
MESH_SIZES = (1, 2, 4, 8)


def _cfg():
    # e6's tiny dense model, TP-divisible everywhere at 8-way:
    # head_dim 16, d_ff 128, vocab 128
    from repro.models.config import ModelConfig
    return ModelConfig(
        arch_id="e8-tiny", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
        norm="rmsnorm", mlp_act="swiglu", rope="rope",
        param_dtype="float32", compute_dtype="float32")


def _make_engine(model, params, mesh):
    from repro.serving import ServeEngine
    return ServeEngine(model, params, batch_size=BATCH, capacity=CAPACITY,
                       max_new_tokens=MAX_NEW, paged=True, block_size=16,
                       prefill_chunk=PROMPT_LEN, burst=8, mesh=mesh)


def _decode_tok_s(eng) -> float:
    """e6-style steady-state window: prefill a full batch to completion,
    warm the burst path, then time pure-decode ticks (no admissions or
    evictions inside the timed region); best of WINDOWS."""
    import numpy as np
    rng = np.random.default_rng(0)
    k = eng.burst
    n_ticks = (MAX_NEW - 10 - k) // k
    best = 0.0
    for _ in range(WINDOWS):
        target = eng.n_prefills + BATCH
        for _ in range(BATCH):
            eng.submit(rng.integers(1, 127, PROMPT_LEN).astype(np.int32))
        while eng.n_prefills < target:
            eng.step()
        eng.step()
        s0 = eng.n_device_steps
        t0 = time.perf_counter()
        for _ in range(n_ticks):
            eng.step()
        wall = time.perf_counter() - t0
        steps = eng.n_device_steps - s0
        assert eng.n_active == BATCH, "slots evicted inside the window"
        best = max(best, steps * BATCH / wall)
        while eng.has_work:
            eng.step()
    return best


def _identity_tokens(eng):
    """Greedy-decode a fixed workload; returns {rid: token list}."""
    import numpy as np
    rng = np.random.default_rng(7)
    for n in (6, 12, 9, 14):
        eng.submit(rng.integers(1, 127, n).astype(np.int32))
    out = {}
    while eng.has_work:
        for r in eng.step():
            out[r.request_id] = list(r.tokens)
    return out


def run() -> List[str]:
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model

    model = build_model(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    n_dev = jax.device_count()
    ref_tokens, ref_tok_s = None, None
    rows = []
    for n in MESH_SIZES:
        if n > n_dev:
            continue
        mesh = None if n == 1 else make_serving_mesh(model=n)
        tok_s = _decode_tok_s(_make_engine(model, params, mesh))
        tokens = _identity_tokens(_make_engine(model, params, mesh))
        if ref_tokens is None:
            ref_tokens, ref_tok_s = tokens, tok_s
        else:
            assert tokens == ref_tokens, \
                f"mesh={n} decoded different tokens than single-device"
        rows.append(f"e8_mesh{n},{1e6 / tok_s:.1f},"
                    f"tok_s={tok_s:.0f};devices={n};paged_burst_k8"
                    f";vs_mesh1=x{tok_s / ref_tok_s:.2f};token_identical=True")
    rows.append(f"e8_summary,{n_dev:.1f},devices={n_dev}"
                f";platform={jax.devices()[0].platform}"
                f";mesh_sizes_token_identical=True;batch={BATCH}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
