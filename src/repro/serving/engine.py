"""Continuous-batching serving engine built on the stream framework.

Requests enter a thread-safe queue (``submit``) and are scheduled into a
fixed array of ``batch_size`` *slots*.  Unlike the fixed-group batcher
this replaces, the decode loop never waits for a full group:

  * finished sequences (hit ``eos_id`` or ``max_new_tokens``) are
    *evicted*, freeing their slot immediately;
  * queued requests *join mid-decode*: the newcomer's prompt is
    left-padded to the batch's current position, prefilled, and its
    slice of the KV cache is spliced into the live cache, so decoding
    of in-flight sequences is never interrupted.

Two cache regimes share this scheduler:

**Dense (legacy / any model)** — all slots share one scalar decode
position (sequences are left-aligned by padding), so a prompt longer
than the current position waits until the position catches up — or
until the batch drains, at which point the engine re-anchors with a
fresh prefill.  The join splice is model-agnostic: the batch axis of
every cache leaf is discovered once via ``jax.eval_shape`` (comparing
cache shapes for batch B vs B+1), so any model exposing
``prefill``/``decode_step`` works — transformer, MLA, hybrid — without
per-model axis annotations.

**Paged (models with ``init_paged_cache``/``paged_step``)** — the KV
cache is one shared pool of fixed-size blocks (``kv_cache.py``); each
slot owns a page table and a true position counter, and attention masks
by per-slot length instead of shared left padding.  Joins no longer pay
a full-position prefill: a newcomer's prompt is consumed in bounded
``prefill_chunk``-token steps *in the same batched calls* that keep
decoding the in-flight slots, so join cost is independent of how long
the batch has been running.  Blocks are reserved worst-case at
admission (prompt + max_new), extended lazily block-by-block as decode
crosses boundaries, and released in full on eviction; a request whose
reservation does not fit stays queued — never a mid-decode allocation
failure.

**Recurrent / hybrid families (mamba, xLSTM, jamba-style stacks)** run
through the same paged scheduler: their attention layers page as above
while each recurrent layer keeps per-sequence state in fixed-size
slabs handed out by a ``StateStore`` (``kv_cache.py``).  Admission is
all-or-nothing across *both* pools — a request needs its worst-case
block reservation AND one free state slab, else it stays queued — and
eviction frees both.  A recycled slab still holds the evictee's state;
the model's paged step zeroes any row whose sequence starts this call
(``lengths == 0``), so state can never leak across requests.  These
families decode *correctly* only here: the dense engine's left-pad
join approximation would run pad tokens through the recurrence and
corrupt the state summary.

**Prefix sharing + copy-on-write (paged only)** — the block pool is
content-addressed: whenever a slot completes a page, the engine
registers the block under the chain digest of the token prefix it
caches.  At admission, a joiner's prompt is matched page-by-page
against resident blocks; matched pages are *mapped* into the new
slot's page table with a refcount bump instead of being re-prefilled
(a final partial page can map onto another sequence's completed tail
block — rows past the joiner's length are masked).  Shared blocks are
immutable: before ``paged_scatter`` would write into a block whose
refcount exceeds one, the engine forks it — acquires a private block,
copies the page's KV, and swaps the page-table entry — so in-flight
slots can never observe each other's writes.  The last matched prompt
token is always re-run through the model (``matched <= len(prompt)-1``)
so the joiner's first sampled token has logits to come from.

**Sampling** — both modes draw next tokens through one shared sampler.
``temperature`` selects the mode: 0 (the default) is exact greedy
argmax, > 0 samples from ``softmax(logits / temperature)`` under
``top_k`` (an explicit ``greedy=True`` forces argmax regardless).
Slot ``b``'s key for its ``t``-th generated token is
``fold_in(fold_in(PRNGKey(seed), request_id), t)`` — a pure function of
the request and step, independent of serving mode, batch composition,
or join timing — so paged and dense serving emit identical token
streams for the same seed.

**Device-resident decode loop** — the hot path never round-trips per
token.  All per-step slot state (page tables, lengths, last tokens,
per-slot ``(rid, step)`` sampling counters, done flags) lives in
persistent device arrays (``DeviceSlotState``) that are mutated in-jit
by one fused **megastep** — model step + sampler + token/length/eos
update, donated buffers — and only rebuilt from the host after a
*structural* event (admission, eviction, block extension, COW fork).
When no admissions, prefill chunks, or forks are pending, the engine
runs **decode bursts**: up to ``burst`` megasteps per host round-trip
in one ``lax.while_loop`` with an all-done early-out, draining sampled
tokens from a device-side ring buffer once per burst — host syncs per
decoded token drop from ~4 to ``1/K``.  Whenever the request queue is
non-empty the engine degrades to ``K = 1`` so join latency is
unchanged; the burst bound is a *traced* scalar, so every K runs the
same compiled loop body and burst output is bit-identical to
single-stepping by construction.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .kv_cache import (ROOT_DIGEST, BlockAllocator, CacheFullError,
                       DeviceSlotState, StateStore, chain_digest)
from .scheduler import SchedRequest, Scheduler
from .steps import (make_decode_step, make_dense_burst, make_paged_burst,
                    make_paged_mixed_step, make_paged_spec_burst,
                    make_paged_spec_mixed_step, make_prefill_step,
                    make_sampler_core)


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    prompt: np.ndarray
    tokens: np.ndarray
    latency_s: float
    # "ok" | "timeout" | "expired" | "cancelled" | "overrun" | "error" —
    # non-ok results carry whatever tokens were generated before the
    # request was failed
    status: str = "ok"
    ttft_s: Optional[float] = None    # submit -> first generated token
    error: Optional[str] = None       # failure message (status "error")
    # time.monotonic() stamps of the request's way through the engine:
    # submit, first admission to a slot, first token sampled, finish.
    # A stamp the request never reached is None.
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None


class _Slot:
    __slots__ = ("rid", "prompt", "tokens", "t_submit", "done", "lane",
                 "deadline", "tag", "status", "t_admit", "t_first",
                 "adm_seq")

    def __init__(self, req: SchedRequest, first_token: int,
                 eos_id: Optional[int], max_new: int, t_admit: float):
        self.rid = req.rid
        self.prompt = req.prompt
        self.tokens: List[int] = [int(first_token)]
        self.t_submit = req.t_submit
        self.t_admit = t_admit
        self.done = (eos_id is not None and int(first_token) == eos_id) \
            or max_new <= 1
        self.lane = req.lane
        self.deadline = req.deadline
        self.tag = req.tag
        self.status = "ok"
        self.t_first: Optional[float] = None
        self.adm_seq = 0


class _PagedSlot:
    """Per-slot decode state in paged mode: true position counter lives
    in the engine's ``_lengths`` array; this tracks ownership."""
    __slots__ = ("rid", "prompt", "tokens", "t_submit", "done", "blocks",
                 "reserve_left", "prefill_off", "digests", "lane",
                 "deadline", "tag", "status", "t_admit", "t_first",
                 "adm_seq", "spec_rounds", "spec_deficit", "spec_prev")

    def __init__(self, req: SchedRequest, blocks: List[int],
                 reserve_left: int, prefill_off: int = 0,
                 digests: Optional[List[bytes]] = None):
        self.rid = req.rid
        self.prompt = req.prompt
        self.tokens: List[int] = []
        self.t_submit = req.t_submit
        self.done = False
        self.blocks = blocks          # physical block ids, page order
        self.reserve_left = reserve_left  # blocks still claimable lazily
        self.prefill_off = prefill_off    # prompt tokens already cached
        self.digests = digests if digests is not None else []  # per full page
        self.lane = req.lane
        self.deadline = req.deadline
        self.tag = req.tag
        self.status = "ok"
        # a restore after preemption keeps the first admission and the
        # first token
        self.t_admit = time.monotonic() if req.t_admit is None \
            else req.t_admit
        self.t_first: Optional[float] = req.t_first
        self.adm_seq = 0
        # host mirrors of the speculative slot-state keys (spec engines
        # only): rounds run (PRNG stream position), draft-cache deficit
        # (0/1 positions the draft KV trails the target), and the token
        # at cache position lengths-1 (the deficit catch-up input)
        self.spec_rounds = 0
        self.spec_deficit = 0
        self.spec_prev = 0


class ServeEngine:
    def __init__(self, model, params, *, batch_size: int = 4,
                 capacity: int = 256, max_new_tokens: int = 16,
                 cache_dtype=jnp.float32, greedy: Optional[bool] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 seed: int = 0, eos_id: Optional[int] = None,
                 paged: Optional[bool] = None, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 share_prefix: Optional[bool] = None,
                 num_state_slots: Optional[int] = None,
                 burst: int = 1, trace_logits: bool = False,
                 mesh=None, retain_cap: Optional[int] = None,
                 retain_ttl_s: Optional[float] = None,
                 draft_model=None, draft_params=None, spec_k: int = 0,
                 kv_dtype: Optional[str] = None,
                 fault_plan=None, max_restarts: int = 3):
        self.model = model
        self.params = params
        self.batch_size = batch_size
        self.capacity = capacity
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        # kv_dtype: storage precision of the serving KV pool.  "f32" /
        # "bf16" simply pin cache_dtype; "int8" switches the paged pool
        # to block-quantized int8 storage with per-row f32 scale leaves
        # (models/attention.gqa_paged_step_quant) — a capacity lever,
        # not a numerics-preserving one, so quantized mode is covered by
        # the drift-tolerance suite instead of bitwise conformance.
        if kv_dtype not in (None, "f32", "bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32', 'bf16' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "f32":
            self.cache_dtype = cache_dtype = jnp.float32
        elif kv_dtype == "bf16":
            self.cache_dtype = cache_dtype = jnp.bfloat16
        self.kv_dtype = kv_dtype
        self._quant = kv_dtype == "int8"
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        # temperature drives the mode: 0 (the default) is exactly the
        # greedy path, > 0 samples; an explicit greedy=True still wins
        self._greedy = (temperature == 0) if greedy is None \
            else bool(greedy) or temperature == 0
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        # paged mode: auto-on when the model implements the protocol
        has_paged = (hasattr(model, "init_paged_cache")
                     and hasattr(model, "paged_step")
                     and (not hasattr(model, "supports_paged")
                          or model.supports_paged()))
        if paged and not has_paged:
            raise ValueError(
                f"paged=True but {type(model).__name__} does not implement "
                "init_paged_cache/paged_step (or supports_paged() is False)")
        self.paged = has_paged if paged is None else bool(paged)
        # tensor-parallel serving over a device mesh: weights are placed
        # by the repo's PartitionSpec rules (heads/FFN/vocab on "model",
        # FSDP over the remaining axes), the paged pool gets
        # head-sharded leaves (see paged_cache_specs), and all host-
        # mirrored slot state is replicated.  The jitted megasteps run
        # unchanged — committed input shardings propagate through them,
        # and every serving entry point enters `with mesh:` so the
        # model's internal with_sharding_constraints activate.
        self.mesh = mesh
        self._replicated = None
        if mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh= requires paged mode: tensor-parallel serving "
                    "shards the paged block pool (the dense per-slot cache "
                    "has no sharded layout)")
            from jax.sharding import NamedSharding, PartitionSpec
            from ..models.sharding import param_shardings
            # a no-op for params created in place (see
            # launch.serve.init_params); others are resharded here
            self.params = jax.device_put(params,
                                         param_shardings(mesh, params))
            self._replicated = NamedSharding(mesh, PartitionSpec())
        self._prefill = jax.jit(make_prefill_step(model, capacity, cache_dtype),
                                static_argnames=())
        self._decode = jax.jit(make_decode_step(model, greedy=True))
        # both modes draw tokens through one sampler core, so a given
        # (seed, request, step) yields the same token either way; the
        # core is inlined into the fused megasteps, and also jitted
        # standalone for the dense admission path
        sampler = make_sampler_core(seed, greedy=self._greedy,
                                    temperature=temperature or 1.0,
                                    top_k=top_k)
        self._sample = jax.jit(sampler)
        # decode bursts: up to `burst` fused megasteps per host
        # round-trip.  `max_burst` (= the init value) sizes the ring
        # buffers and is static; `self.burst` may be lowered at runtime
        # and is traced, so every K <= max_burst runs one compilation.
        if burst < 1:
            raise ValueError(f"burst must be >= 1, got {burst}")
        self.max_burst = int(burst)
        self.burst = int(burst)
        # request queue (two priority lanes) + in-flight slot map
        self.scheduler = Scheduler()
        self._slots: List[Optional[_Slot]] = [None] * batch_size
        self._cache = None
        self._pos = 0                 # shared aligned decode position
        self._batch_axes = None       # cache pytree of batch-axis indices
        self._lock = threading.Lock()
        self._next_rid = 0
        # completed results, keyed by rid until a wait() collects them;
        # the condition variable wakes concurrent waiters, and the step
        # lock elects exactly one thread at a time to drive step()
        self._results: Dict[int, GenerationResult] = {}
        self._results_cv = threading.Condition()
        self._step_lock = threading.Lock()
        self._adm_seq = 0             # admission order (preemption picks
        #                               the youngest batch-lane slot)
        # optional token-streaming hook: stream_cb(rid, new_tokens) fires
        # whenever generated tokens for a request reach the host (once
        # per slot per burst drain) — the network front door uses it to
        # stream tokens back per-request before the batch completes
        self.stream_cb = None
        # paged-mode state: block pool + per-slot page tables / lengths
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        if share_prefix and not self.paged:
            raise ValueError(
                "share_prefix=True requires paged mode (the dense cache has "
                "no block pool to share)")
        # recurrent state slabs disable prefix sharing: a slab summarizes
        # the whole prefix, so resident KV pages alone cannot seed a joiner
        sharable = not self.paged or bool(
            getattr(model, "supports_prefix_sharing", lambda: True)())
        if share_prefix and not sharable:
            raise ValueError(
                f"share_prefix=True but {type(model).__name__} "
                f"(family={getattr(getattr(model, 'cfg', None), 'family', '?')!r}) "
                "has recurrent layers whose state cannot be shared across "
                "requests: a mamba/xLSTM state slab summarizes its entire "
                "prefix, so mapping resident KV pages cannot reconstruct "
                "it.  Run with share_prefix=False (or leave it on auto).")
        self.share_prefix = (self.paged and sharable) if share_prefix is None \
            else bool(share_prefix)
        # speculative (draft-verify) decoding: a small draft model runs
        # spec_k tokens ahead inside each decode burst round, the target
        # verifies every drafted position in ONE T = spec_k+1 paged
        # step, and accept/reject follows the rejection-sampling rule
        # (see steps.make_paged_spec_burst) — the output distribution is
        # provably the target's, and greedy output is token-identical to
        # non-speculative decode by construction.
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self.draft_params = draft_params
        self._spec = self.spec_k > 0
        if self._spec:
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "spec_k > 0 requires draft_model= and draft_params= "
                    "(a small model sharing the target's vocabulary)")
            if not self.paged:
                raise ValueError(
                    "spec_k > 0 requires paged mode: speculative rollback "
                    "is arithmetic on per-slot lengths, which only the "
                    "block-paged cache tracks")
            if mesh is not None:
                raise NotImplementedError(
                    "speculative decoding under mesh= is not implemented "
                    "yet: the draft pool needs its own sharding specs and "
                    "the accept rule a replicated gather per drafted "
                    "position")
            if prefill_chunk < 2:
                raise ValueError(
                    "spec_k > 0 requires prefill_chunk >= 2: the draft's "
                    "deficit catch-up feeds two tokens through the mixed "
                    f"megastep, got prefill_chunk={prefill_chunk}")
            for role, m in (("target", model), ("draft", draft_model)):
                sup = getattr(m, "supports_speculative", None)
                ok = sup() if sup is not None else not bool(
                    getattr(m, "has_recurrent_state", lambda: False)())
                if not ok:
                    raise ValueError(
                        f"spec_k > 0 but the {role} model "
                        f"{type(m).__name__} (family="
                        f"{getattr(getattr(m, 'cfg', None), 'family', '?')!r}) "
                        "has recurrent layers: rejected tokens roll back by "
                        "arithmetic on per-slot lengths, and a recurrent "
                        "state slab advanced through rejected tokens cannot "
                        "be rolled back.  Serve this family with spec_k=0.")
            tcfg = getattr(model, "cfg", None)
            dcfg = getattr(draft_model, "cfg", None)
            if tcfg is not None and dcfg is not None \
                    and tcfg.vocab_size != dcfg.vocab_size:
                raise ValueError(
                    f"draft/target vocab mismatch: target {tcfg.vocab_size} "
                    f"vs draft {dcfg.vocab_size} — speculative decoding "
                    "requires a shared tokenizer/vocabulary")
            if share_prefix:
                raise ValueError(
                    "share_prefix=True is incompatible with spec_k > 0: the "
                    "draft KV rides the same page tables as the target, but "
                    "COW forks and content registration only cover the "
                    "target pool.  Leave share_prefix on auto (speculative "
                    "mode disables it) or set it False.")
            self.share_prefix = False
        if self._quant:
            if not self.paged:
                raise ValueError(
                    "kv_dtype='int8' requires paged mode: quantized KV "
                    "lives in the shared block pool (the dense per-slot "
                    "cache stays full precision)")
            if self._spec:
                raise ValueError(
                    "kv_dtype='int8' is incompatible with spec_k > 0: the "
                    "draft pool and the greedy verify-identity guarantee "
                    "are not quantization-aware.  Serve quantized without "
                    "speculation (spec_k=0).")
            if mesh is not None:
                raise NotImplementedError(
                    "kv_dtype='int8' under mesh= is not implemented yet: "
                    "the f32 scale pools need audited sharding specs "
                    "before the quantized pool can be distributed")
            sig = inspect.signature(model.init_paged_cache)
            if "kv_dtype" not in sig.parameters:
                raise ValueError(
                    f"kv_dtype='int8' but {type(model).__name__}."
                    "init_paged_cache does not accept kv_dtype= (the model "
                    "does not implement quantized pools)")
        self._pages_per_slot = -(-capacity // block_size)
        if num_blocks is None:
            num_blocks = batch_size * self._pages_per_slot
        self.allocator = BlockAllocator(num_blocks, block_size,
                                        retain_cap=retain_cap,
                                        retain_ttl_s=retain_ttl_s) \
            if self.paged else None
        # recurrent families: per-slot state slabs beside the block pool
        needs_state = self.paged and bool(
            getattr(model, "has_recurrent_state", lambda: False)())
        self.num_state_slots = (batch_size if num_state_slots is None
                                else num_state_slots) if needs_state else 0
        self.state_store = StateStore(self.num_state_slots) \
            if needs_state else None
        self._page_table = np.zeros((batch_size, self._pages_per_slot),
                                    np.int32)
        self._lengths = np.zeros((batch_size,), np.int32)
        self._state_slots = np.zeros((batch_size,), np.int32)
        self._reserved = 0            # lazily-claimable blocks promised out
        copy_fn = getattr(model, "copy_paged_block", _generic_copy_paged_block)
        self._copy_block = jax.jit(copy_fn, donate_argnums=(0,)) \
            if self.paged else None
        # preemption spill/restore: gather pages+slab to host / scatter
        # them back at new physical homes.  Models without the protocol
        # fall back to the generic block-axis convention (attn-only);
        # recurrent stacks without it cannot be preempted.
        self._gather_pages = None
        self._scatter_pages = None
        if self.paged:
            gather = getattr(model, "gather_paged_pages", None)
            scatter = getattr(model, "scatter_paged_pages", None)
            if gather is not None and scatter is not None:
                self._gather_pages = jax.jit(gather)
                self._scatter_pages = jax.jit(scatter, donate_argnums=(0,))
            elif not needs_state:
                self._gather_pages = jax.jit(_generic_gather_pages)
                self._scatter_pages = jax.jit(_generic_scatter_pages,
                                              donate_argnums=(0,))
        # the draft pool spills/restores beside the target pool with its
        # own (draft-shaped) gather/scatter
        self._gather_draft = self._scatter_draft = None
        if self._spec:
            g = getattr(draft_model, "gather_paged_pages", None)
            s = getattr(draft_model, "scatter_paged_pages", None)
            self._gather_draft = jax.jit(g) if g is not None \
                else jax.jit(_generic_gather_pages)
            self._scatter_draft = jax.jit(s, donate_argnums=(0,)) \
                if s is not None \
                else jax.jit(_generic_scatter_pages, donate_argnums=(0,))
        self._paged_cache = None
        self._draft_cache = None
        self._kv_bytes_per_block_cache = None
        # optional per-request logit recording (conformance tests)
        self.trace_logits = trace_logits
        self.logit_trace: Dict[int, List[np.ndarray]] = {}
        # fused megasteps: model step + sampler + slot-state update in
        # one jit, cache AND slot state donated — the pool is rewritten
        # every tick, and without donation XLA copies all
        # num_blocks*block_size K/V per token
        if self.paged and self._spec:
            self._mixed_fn = jax.jit(
                make_paged_spec_mixed_step(model, draft_model, sampler,
                                           eos_id=eos_id,
                                           max_new=max_new_tokens,
                                           capacity=capacity),
                donate_argnums=(2, 3, 4))
            self._burst_fn = jax.jit(
                make_paged_spec_burst(model, draft_model, eos_id=eos_id,
                                      max_new=max_new_tokens,
                                      capacity=capacity,
                                      spec_k=self.spec_k,
                                      k_static=self.max_burst, seed=seed,
                                      greedy=self._greedy,
                                      temperature=temperature or 1.0,
                                      top_k=top_k, trace=trace_logits),
                donate_argnums=(2, 3, 4))
        elif self.paged:
            self._mixed_fn = jax.jit(
                make_paged_mixed_step(model, sampler, eos_id=eos_id,
                                      max_new=max_new_tokens,
                                      capacity=capacity),
                donate_argnums=(1, 2))
            self._burst_fn = jax.jit(
                make_paged_burst(model, sampler, eos_id=eos_id,
                                 max_new=max_new_tokens, capacity=capacity,
                                 k_static=self.max_burst,
                                 trace=trace_logits),
                donate_argnums=(1, 2))
        else:
            self._mixed_fn = None
            self._burst_fn = jax.jit(
                make_dense_burst(model, sampler, eos_id=eos_id,
                                 max_new=max_new_tokens,
                                 k_static=self.max_burst,
                                 trace=trace_logits),
                donate_argnums=(1, 2))
        # device-resident slot state: uploaded only after structural
        # host mutations, otherwise mutated in-jit and adopted back
        # (replicated over the mesh — page tables / lengths / tokens are
        # global control state every device must see in full)
        self._dev = DeviceSlotState(
            put=(lambda v: jax.device_put(np.asarray(v), self._replicated))
            if mesh is not None else None)
        # scheduler counters
        self.n_requests = 0
        self.n_prefills = 0
        self.n_joins = 0              # requests admitted mid-decode
        self.n_evictions = 0          # slots freed by eos/max_new
        self.n_prefill_chunks = 0     # paged: bounded prefill steps run
        self.n_prefix_hits = 0        # paged: admissions that mapped blocks
        self.n_shared_tokens = 0      # prompt tokens served from shared blocks
        self.n_cow_forks = 0          # shared blocks forked before a write
        # scheduler counters
        self.n_preemptions = 0        # batch-lane slots spilled to host
        self.n_restores = 0           # preempted requests re-admitted
        self.n_expired = 0            # queued requests past their deadline
        # decode-loop counters (see loop_stats())
        self.n_bursts = 0             # burst launches (>= 1 device step each)
        self.n_device_steps = 0       # fused megasteps executed on device
        self.n_host_syncs = 0         # decode-loop device->host drains
        self.n_burst_early_exits = 0  # bursts cut short by all-done
        # paged attention's reach (see loop_stats()): pages it read, and
        # pages the page tables could address, summed over device steps
        self.n_attn_pages_live = 0
        self.n_attn_pages_capacity = 0
        # speculative-decode counters (see loop_stats())
        self.n_spec_rounds = 0        # draft+verify rounds executed
        self.n_spec_tokens = 0        # tokens emitted by those rounds
        self.n_draft_proposed = 0     # draft tokens offered to the verifier
        self.n_draft_accepted = 0     # draft tokens the verifier accepted
        # per-round accepted-length histogram: bin a counts rounds that
        # accepted exactly a draft tokens (a in [0, spec_k])
        self.spec_accept_hist = [0] * (self.spec_k + 1) if self._spec else []
        # fault tolerance: injectable fault plan (serving.faults, duck-
        # typed so None costs one check) + bounded-restart accounting for
        # non-attributable step failures
        self.fault_plan = fault_plan
        self.max_restarts = int(max_restarts)
        self.n_step_failures = 0      # step() exceptions caught
        self.n_restarts = 0           # engine pool rebuilds performed
        self.n_cancelled = 0          # requests cancelled via cancel()
        self._consec_failures = 0     # resets on every clean step
        self._tick = 0                # step() calls: the trace's step number

    # -- synchronous fixed batch API (kept for benchmarks/back-compat) ------
    def generate_batch(self, prompts: np.ndarray,
                       extra_embeds=None) -> np.ndarray:
        """prompts: (B, S) int32 -> generated (B, max_new_tokens).

        Always decodes greedily (the continuous API carries the seeded
        sampling path)."""
        B, S = prompts.shape
        assert B == self.batch_size, (B, self.batch_size)
        with self._sharding_ctx():
            return self._generate_batch_impl(prompts, extra_embeds)

    def _generate_batch_impl(self, prompts, extra_embeds):
        B, S = prompts.shape
        logits, cache = self._prefill(self.params, jnp.asarray(prompts),
                                      extra_embeds)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out = [np.asarray(token)]
        pos = S
        for _ in range(self.max_new_tokens - 1):
            token, _, cache = self._decode(self.params, cache, token,
                                           jnp.int32(pos))
            out.append(np.asarray(token))
            pos += 1
        self.n_requests += B
        return np.concatenate(out, axis=1)

    # -- continuous batching ------------------------------------------------
    def submit(self, prompt: np.ndarray, *, lane: str = "interactive",
               deadline: Optional[float] = None, tag: Any = None) -> int:
        """Enqueue a request; returns its request id (thread-safe).

        ``lane`` picks the priority lane (``"interactive"`` admits ahead
        of any queued ``"batch"`` work and may preempt running batch
        slots); ``deadline`` is a relative TTFT budget in seconds — a
        request still queued when it elapses fails with status
        ``"expired"``; ``tag`` is an opaque caller handle carried into
        nothing engine-side (the network layer uses it for routing)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            raise ValueError(f"prompt must be non-empty 1-D, got {prompt.shape}")
        if prompt.shape[0] > self.capacity:
            raise ValueError(
                f"prompt length {prompt.shape[0]} exceeds KV-cache capacity "
                f"{self.capacity}; raise capacity= or truncate the prompt")
        # vocab validation at the gate: an out-of-range token would index
        # past the embedding table inside a jitted megastep, which can
        # poison a whole batch — reject it before it ever owns a slot
        vocab = getattr(getattr(self.model, "cfg", None), "vocab_size", None)
        if vocab is not None and (int(prompt.min()) < 0
                                  or int(prompt.max()) >= int(vocab)):
            raise ValueError(
                f"prompt tokens outside the model vocab [0, {vocab}) "
                f"(min {int(prompt.min())}, max {int(prompt.max())})")
        now = time.monotonic()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.scheduler.push(SchedRequest(
                rid, prompt, lane=lane,
                deadline=None if deadline is None else now + deadline,
                tag=tag, t_submit=now))
            self.n_requests += 1
        return rid

    @property
    def n_active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_work(self) -> bool:
        with self._lock:
            return self.scheduler.pending or self.n_active > 0

    def _finish(self, res: GenerationResult) -> None:
        """Record a completed result and wake any wait()ers."""
        with self._results_cv:
            self._results[res.request_id] = res
            self._results_cv.notify_all()

    def _make_result(self, slot, now: float) -> GenerationResult:
        return GenerationResult(
            request_id=slot.rid, prompt=slot.prompt,
            tokens=np.asarray(slot.tokens, np.int32),
            latency_s=now - slot.t_submit, status=slot.status,
            ttft_s=None if slot.t_first is None
            else slot.t_first - slot.t_submit,
            t_submit=slot.t_submit, t_admit=slot.t_admit,
            t_first=slot.t_first, t_finish=now)

    def pool_stats(self) -> Optional[Dict[str, int]]:
        """Block-pool occupancy incl. shared vs private split (paged),
        plus state-slab occupancy for recurrent families, plus the pool
        footprint: ``kv_dtype`` (storage precision), ``bytes_per_block``
        (all attn K/V leaves — scales included for int8 — per physical
        block) and ``pool_bytes`` — the numbers the capacity planning in
        the quantization benchmark (``e10_quant``) is driven by."""
        if self.allocator is None:
            return None
        stats = self.allocator.stats()
        stats["n_reserved"] = self._reserved
        stats["kv_dtype"] = self.kv_dtype or {
            "float32": "f32", "bfloat16": "bf16",
        }.get(jnp.dtype(self.cache_dtype).name,
              jnp.dtype(self.cache_dtype).name)
        stats["bytes_per_block"] = self.kv_bytes_per_block()
        stats["pool_bytes"] = \
            stats["bytes_per_block"] * self.allocator.num_blocks
        if self.state_store is not None:
            s = self.state_store.stats()
            stats["num_state_slots"] = s["num_slots"]
            stats["n_state_free"] = s["n_free"]
            stats["n_state_live"] = s["n_live"]
        return stats

    def kv_bytes_per_block(self) -> int:
        """HBM bytes one physical block costs across every attn layer's
        pool leaves (K + V, plus the f32 scale slivers under
        ``kv_dtype='int8'``).  Computed from ``jax.eval_shape`` of the
        model's pool constructor — no pool has to exist yet — and keyed
        on the leaf *names* (k/v/k_scale/v_scale) so recurrent state
        slabs (sized by slots, not blocks) never pollute the figure."""
        if self._kv_bytes_per_block_cache is None:
            if self.allocator is None:
                return 0
            kw = self._paged_cache_kwargs()
            struct = jax.eval_shape(
                lambda: self.model.init_paged_cache(
                    self.allocator.num_blocks, self.block_size,
                    dtype=self.cache_dtype, **kw))
            kv_names = {"k", "v", "k_scale", "v_scale"}

            def leaf_name(path):
                for p in reversed(path):
                    if isinstance(p, jax.tree_util.DictKey):
                        return p.key
                return None

            def nbytes(leaf):
                return int(np.prod(leaf.shape)) * jnp.dtype(
                    leaf.dtype).itemsize

            leaves = jax.tree_util.tree_flatten_with_path(struct)[0]
            tot = sum(nbytes(l) for path, l in leaves
                      if leaf_name(path) in kv_names)
            if tot == 0:    # model without the k/v naming convention
                tot = sum(nbytes(l) for _, l in leaves)
            self._kv_bytes_per_block_cache = tot // self.allocator.num_blocks
        return self._kv_bytes_per_block_cache

    def loop_stats(self) -> Dict[str, int]:
        """Decode-loop efficiency counters: device steps vs host drains
        vs state uploads.  ``n_host_syncs / n_device_steps`` is the
        host-syncs-per-token figure the burst mode drives toward 1/K;
        ``n_state_uploads`` counts host->device slot-state rebuilds
        (structural events only — steady decode adds none).  Paged
        engines add ``attn_kernel``, the path their attention takes
        (``models.attention.paged_attention_path``: ``"pallas"`` or
        ``"jnp"``), and over the mixed steps and plain bursts run,
        ``n_attn_pages_live`` — the pages each slot with work holds
        after the step, ``ceil((lengths + t_valid) / block_size)``,
        which is what the kernel reads per layer — against
        ``n_attn_pages_capacity``, batch x pages per slot a step, which
        is what the jnp path gathers."""
        out = {"burst": self.burst, "max_burst": self.max_burst,
               "n_bursts": self.n_bursts,
               "n_device_steps": self.n_device_steps,
               "n_host_syncs": self.n_host_syncs,
               "n_burst_early_exits": self.n_burst_early_exits,
               "n_state_uploads": self._dev.n_uploads}
        if self.paged:
            out.update(attn_kernel=self._attn_kernel(),
                       n_attn_pages_live=self.n_attn_pages_live,
                       n_attn_pages_capacity=self.n_attn_pages_capacity)
        if self._spec:
            out.update(
                spec_k=self.spec_k,
                n_spec_rounds=self.n_spec_rounds,
                n_spec_tokens=self.n_spec_tokens,
                n_draft_proposed=self.n_draft_proposed,
                n_draft_accepted=self.n_draft_accepted,
                spec_accept_hist=list(self.spec_accept_hist),
                spec_accept_rate=self.n_draft_accepted
                / max(1, self.n_draft_proposed))
        return out

    def _attn_kernel(self) -> str:
        from ..models.attention import paged_attention_path
        dtype = jnp.int8 if self._quant else self.cache_dtype
        with self._sharding_ctx():
            return paged_attention_path(dtype)

    def _count_attn_pages(self, extents: np.ndarray) -> None:
        """Add one device step's pages: ``extents`` holds each working
        slot's cached length after the step."""
        self.n_attn_pages_live += int(
            (-(-extents // self.block_size)).sum())
        self.n_attn_pages_capacity += self._page_table.size

    def compile_stats(self) -> Dict[str, int]:
        """Compilation counts of the jitted hot-path functions.  The
        burst megastep must compile exactly once per engine (its K
        bound is traced); the mixed megastep once (T is pinned to
        ``prefill_chunk``).  CI asserts these to catch silent recompile
        regressions."""
        out = {}
        for name, fn in (("megastep_burst", self._burst_fn),
                         ("megastep_mixed", self._mixed_fn),
                         ("prefill", self._prefill)):
            if fn is None:
                continue
            out[name] = fn._cache_size()
        return out

    def _sharding_ctx(self):
        """Mesh context for the jitted serving paths.  Tracing under
        ``with mesh:`` is what activates every ``constrain(...)`` inside
        the model / megasteps (they no-op without an active mesh), so
        all entry points that can trigger a jit call enter it."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def step(self) -> List[GenerationResult]:
        """Admit what fits, run one decode burst (or a mixed
        prefill+decode megastep), evict what finished.

        Returns results for requests that completed during this step.

        A step exception is *non-attributable* — there is no way to
        know which resident request poisoned the megastep — so the
        engine restarts: live slots are spilled to host and re-queued
        (the PR 6 preemption path, bit-identical on restore), the
        device pools and allocator are rebuilt, and serving continues.
        Restarts are bounded by ``max_restarts`` *consecutive*
        failures; past that every in-flight and queued request is
        failed and the exception propagates.

        Each tick is an ``engine_step`` span in a profiler trace, with
        its phases (``engine.admit``, ``engine.evict``,
        ``engine.prepare``, ``engine.dispatch``, ``engine.drain``,
        ``engine.emit``) as spans inside it on the host's plane.
        """
        fault = self.fault_plan.fire("engine_step") if self.fault_plan \
            else None
        self._tick += 1
        try:
            if fault is not None and fault.action == "raise":
                raise fault.make_exc()
            with self._sharding_ctx(), StepTraceAnnotation(
                    "engine_step", step_num=self._tick):
                out = self._step_impl()
        except Exception as exc:
            return self._handle_step_failure(exc)
        self._consec_failures = 0
        return out

    def _handle_step_failure(self, exc: Exception) -> List[GenerationResult]:
        """Recover from a non-attributable step exception: bounded
        restart (spill survivors, rebuild pools) or — past the budget —
        fail everything and re-raise."""
        self.n_step_failures += 1
        self._consec_failures += 1
        if self._consec_failures > self.max_restarts:
            now = time.monotonic()
            msg = f"engine wedged after {self.n_restarts} restarts: {exc}"
            with self._lock:
                queued = []
                for req in list(self.scheduler.candidates()):
                    self.scheduler.remove(req)
                    queued.append(req)
            for req in queued:
                self._finish(GenerationResult(
                    request_id=req.rid, prompt=req.prompt,
                    tokens=np.asarray(req.tokens, np.int32),
                    latency_s=now - req.t_submit, status="error", error=msg))
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                self._finish(GenerationResult(
                    request_id=slot.rid, prompt=slot.prompt,
                    tokens=np.asarray(slot.tokens, np.int32),
                    latency_s=now - slot.t_submit, status="error", error=msg))
                self._slots[i] = None
            self._reset_pools()        # nothing leaks even in death
            raise exc
        self.n_restarts += 1
        self._restart()
        return []

    def _restart(self) -> None:
        """Rebuild the serving pools after a step failure.

        Paged mode: every live slot is spilled via the preemption path
        (decode slots gather their pages/slab to host; mid-prefill
        slots simply restart) and re-queued at its lane's front, then
        the device caches, allocator, and state store are rebuilt from
        scratch — donation means the old cache arrays may already be
        deleted, and the content table would advertise garbage over a
        fresh pool either way.  A slot whose spill itself fails (e.g.
        its pages lived in a donated-away buffer) is failed alone with
        status ``"error"``.  Dense mode has no spill path: in-flight
        slots are failed, queued work survives untouched."""
        now = time.monotonic()
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            spilled = False
            if self.paged and not slot.done:
                try:
                    with self._sharding_ctx():
                        self._preempt_slot(i)
                    spilled = True
                except Exception:
                    pass               # unsalvageable: fail it below
            if not spilled:
                self._finish(GenerationResult(
                    request_id=slot.rid, prompt=slot.prompt,
                    tokens=np.asarray(slot.tokens, np.int32),
                    latency_s=now - slot.t_submit, status="error",
                    error="lost in engine restart"))
            self._slots[i] = None
        self._reset_pools()

    def _reset_pools(self) -> None:
        """Rebuild device caches + host accounting from scratch (all
        slots must already be empty)."""
        if self.paged:
            old = self.allocator
            self.allocator = BlockAllocator(
                old.num_blocks, old.block_size,
                retain_cap=old.retain_cap, retain_ttl_s=old.retain_ttl_s)
            if self.state_store is not None:
                self.state_store = StateStore(self.num_state_slots)
            self._paged_cache = None
            self._draft_cache = None
        else:
            self._cache = None
            self._pos = 0
        self._reserved = 0
        self._page_table[:, :] = 0
        self._lengths[:] = 0
        self._state_slots[:] = 0
        self._dev.mark_dirty()

    def cancel(self, rid: int, status: str = "cancelled") -> bool:
        """Cancel one request wherever it is — queued, mid-prefill, or
        mid-decode-burst (the drained ring is replayed up to the cancel
        point, so its result carries every token generated before the
        cancel landed).  Its blocks, state slab, and any retained
        content-table registrations are freed.  Returns True if the
        request was live and is now terminal with ``status``; False if
        it was unknown or already finished (the existing result is left
        for its waiter)."""
        with self._results_cv:
            if rid in self._results:
                return False
        self._cancel([rid], status)
        with self._results_cv:
            done = rid in self._results
        if done:
            self.n_cancelled += 1
        return done

    def inflight_rids(self) -> List[int]:
        """Rids with no result yet: queued plus resident in a slot."""
        with self._lock:
            queued = [req.rid for req in self.scheduler.candidates()]
        return queued + [s.rid for s in self._slots if s is not None]

    def _step_impl(self) -> List[GenerationResult]:
        if self.paged:
            return self._step_paged()
        with TraceAnnotation("engine.admit"):
            self._admit()
        with TraceAnnotation("engine.evict"):
            finished = self._evict()
        if self.n_active == 0:
            return finished
        if self._pos >= self.capacity:
            # cache exhausted: truncate everything still in flight
            for slot in self._slots:
                if slot is not None:
                    slot.done = True
            return finished + self._evict()
        with TraceAnnotation("engine.prepare"):
            with self._lock:
                pending = self.scheduler.pending
            # queue non-empty -> single-step so the next eviction admits
            # at once; otherwise burst, capped at the cache strip's
            # remainder
            k = 1 if pending else min(self.burst, self.max_burst)
            k = max(1, min(k, self.capacity - self._pos))
            st = self._dev.device(self._dense_state)
        with TraceAnnotation("engine.dispatch", k=k):
            out = self._burst_fn(self.params, self._cache, st,
                                 jnp.int32(self._pos), np.int32(k))
        self._cache = out[0]
        self._dev.adopt(out[1])
        self._drain_burst(out[2], out[3],
                          out[4] if self.trace_logits else None,
                          k=k, paged=False)
        with TraceAnnotation("engine.evict"):
            return finished + self._evict()

    def serve(self, requests: List[np.ndarray], timeout_s: float = 120.0,
              lane: str = "interactive") -> List[GenerationResult]:
        """Serve via continuous batching; results in request order.

        On timeout the results completed before the deadline are
        returned as-is and every unfinished request is failed with
        status ``"timeout"`` (its tokens so far attached) — nothing is
        dropped and the engine's pool is left clean."""
        rids = [self.submit(r, lane=lane) for r in requests]
        return self.wait(rids, timeout_s=timeout_s)

    def wait(self, rids: List[int],
             timeout_s: Optional[float] = None) -> List[GenerationResult]:
        """Block until every request in ``rids`` has a result, driving
        ``step()`` whenever no other thread is.  Safe to call from
        multiple threads over one engine: all submissions share the
        scheduler, exactly one waiter steps at a time, and each waiter
        collects (and removes) only its own results."""
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        while True:
            with self._results_cv:
                if all(r in self._results for r in rids):
                    break
                missing = [r for r in rids if r not in self._results]
            if deadline is not None and time.monotonic() >= deadline:
                self._cancel(missing, "timeout")
                break
            if self._step_lock.acquire(blocking=False):
                try:
                    if self.has_work:
                        self.step()
                    else:
                        time.sleep(0.001)
                finally:
                    self._step_lock.release()
            else:
                with self._results_cv:
                    self._results_cv.wait(timeout=0.005)
        with self._results_cv:
            return [self._results.pop(rid) for rid in rids
                    if rid in self._results]

    def _cancel(self, rids: List[int], status: str) -> None:
        """Fail every request in ``rids``: queued ones are popped with
        their (possibly preempted) tokens attached, in-flight ones are
        evicted with whatever they generated so far.  Runs under the
        step lock so no megastep is mid-flight while slots are torn
        down."""
        rids = set(rids)
        if not rids:
            return
        with self._step_lock:
            now = time.monotonic()
            with self._lock:
                popped = [self.scheduler.pop_rid(rid) for rid in rids]
            for req in popped:
                if req is None:
                    continue
                self._finish(GenerationResult(
                    request_id=req.rid, prompt=req.prompt,
                    tokens=np.asarray(req.tokens, np.int32),
                    latency_s=now - req.t_submit, status=status))
            dirty = False
            dead_blocks: List[int] = []
            for slot in self._slots:
                if slot is not None and slot.rid in rids:
                    slot.status = status
                    slot.done = True
                    if self.paged:
                        dead_blocks += list(slot.blocks)
                    dirty = True
            if dirty:
                self._evict_paged() if self.paged else self._evict()
                # a cancelled request's pages must not linger as
                # retained prefix bait: retire any of its blocks that
                # eviction parked on the retained list (blocks still
                # shared with a live slot are untouched)
                for b in dead_blocks:
                    self.allocator.retire(b)

    def as_pipeline_filter(self, *, use_meta: bool = False,
                           on_submit=None, timeout_s: Optional[float] = None):
        """Adapter: (n, S) prompt batch -> (n, max_new_tokens) generations.

        Row order in == row order out, so TensorUnbatcher downstream can
        restore per-request pts/meta.  Rows shorter than max_new (early
        eos) are right-padded with eos_id (or 0).

        With ``use_meta`` the returned callable accepts the per-row meta
        dicts a ``pass_meta`` TensorFilter forwards: each row's
        ``meta["query"]`` may carry ``prompt_len`` (strip transport
        left-padding), ``lane``, ``deadline`` (relative seconds) and
        ``tag``; after serving, ``status`` / ``n_tokens`` and the
        engine stamps ``t_submit`` / ``t_admit`` / ``t_first`` /
        ``t_finish`` are written back into the meta for the downstream
        sink.
        ``on_submit(rid, meta)`` fires immediately after each row is
        submitted — before any token is generated — so a streaming
        front door can route ``stream_cb`` tokens by request id.  A row
        whose meta is None is a batch-bucket pad row (``TensorFilter``
        pads a batch up to its bucket) and is not served."""
        pad = self.eos_id if self.eos_id is not None else 0

        def fn(prompts, metas=None):
            prompts = np.asarray(prompts, np.int32)
            with_meta = use_meta and metas is not None
            ms = list(metas) if with_meta else [None] * len(prompts)
            rids: List[Optional[int]] = []
            for row, m in zip(prompts, ms):
                if with_meta and m is None:
                    rids.append(None)
                    continue
                q = m.get("query", {}) if isinstance(m, dict) else {}
                plen = int(q.get("prompt_len", 0)) or row.shape[0]
                # per-row isolation: a poison prompt (bad shape, vocab
                # overflow, injected "submit" fault) fails only its own
                # row — the rest of the batch is served normally
                try:
                    f = self.fault_plan.fire("submit") if self.fault_plan \
                        else None
                    if f is not None and f.action == "raise":
                        raise f.make_exc()
                    rid = self.submit(row[row.shape[0] - plen:],
                                      lane=q.get("lane", "interactive"),
                                      deadline=q.get("deadline"),
                                      tag=q.get("tag"))
                except Exception as exc:
                    rids.append(None)
                    if isinstance(m, dict):
                        m.update(status="error", error=str(exc), n_tokens=0)
                    continue
                rids.append(rid)
                if isinstance(m, dict):
                    m["rid"] = rid
                if on_submit is not None:
                    on_submit(rid, m)
            live = [r for r in rids if r is not None]
            err = None
            try:
                f = self.fault_plan.fire("worker") if self.fault_plan \
                    else None
                if f is not None and f.action == "raise":
                    raise f.make_exc()
                results = self.wait(live, timeout_s=timeout_s)
            except Exception as exc:
                # worker-level failure after submission: fail exactly
                # this batch's requests (with a clean two-pool free) and
                # surface the message — other workers' requests and the
                # engine itself keep going
                err = str(exc)
                self._cancel(live, "error")
                with self._results_cv:
                    results = [self._results.pop(r) for r in live
                               if r in self._results]
            by_id = {r.request_id: r for r in results}
            out = np.full((len(rids), self.max_new_tokens), pad, np.int32)
            for i, rid in enumerate(rids):
                if rid is None:
                    continue          # failed at submit; meta already set
                r = by_id.get(rid)
                if r is None:
                    if isinstance(ms[i], dict):
                        ms[i].update(status="error", n_tokens=0,
                                     error=err or "request lost")
                    continue
                out[i, : len(r.tokens)] = r.tokens
                if isinstance(ms[i], dict):
                    ms[i].update(status=r.status,
                                 n_tokens=int(len(r.tokens)),
                                 t_submit=r.t_submit, t_admit=r.t_admit,
                                 t_first=r.t_first, t_finish=r.t_finish)
                    if r.status == "error":
                        ms[i]["error"] = r.error or err or "request failed"
            return out
        return fn

    # -- sampling -----------------------------------------------------------
    def _sample_rows(self, logits, rids: np.ndarray,
                     steps: np.ndarray) -> np.ndarray:
        """Draw one token per batch row through the shared sampler
        (admission path only — the decode loop samples inside the fused
        megastep).  ``rids``/``steps`` are (B,) int32 vectors; the
        per-row key is derived from them inside the jit, so a slot's
        draw is a pure function of (seed, request, step) —
        serving-mode independent.  Idle rows carry (0, 0); callers only
        consume rows they populated (greedy ignores them entirely)."""
        return np.asarray(self._sample(jnp.asarray(logits),
                                       jnp.asarray(rids, dtype=jnp.int32),
                                       jnp.asarray(steps, dtype=jnp.int32)))

    # -- device-resident slot state -----------------------------------------
    def _dense_state(self) -> Dict[str, np.ndarray]:
        """Host rebuild of the dense-mode device state (dirty path)."""
        B = self.batch_size
        tokens = np.zeros((B,), np.int32)
        rids = np.zeros((B,), np.int32)
        steps = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            rids[i] = s.rid
            steps[i] = len(s.tokens)
            if s.tokens:
                tokens[i] = s.tokens[-1]
            active[i] = not s.done
        return {"tokens": tokens, "rids": rids, "steps": steps,
                "active": active}

    def _paged_state(self) -> Dict[str, np.ndarray]:
        """Host rebuild of the paged-mode device state (dirty path)."""
        B = self.batch_size
        tokens = np.zeros((B,), np.int32)
        rids = np.zeros((B,), np.int32)
        steps = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            rids[i] = s.rid
            steps[i] = len(s.tokens)
            if s.tokens:
                tokens[i] = s.tokens[-1]
            # decoding = prefill complete, first token sampled, not done,
            # cache strip not exhausted (the burst body writes at
            # `lengths` before its own done check, so an active row must
            # always have room for one token)
            active[i] = (not s.done and s.prefill_off >= len(s.prompt)
                         and len(s.tokens) > 0
                         and int(self._lengths[i]) < self.capacity)
        out = {"tokens": tokens, "rids": rids, "steps": steps,
               "active": active, "page_table": self._page_table,
               "lengths": self._lengths, "state_slots": self._state_slots}
        if self._spec:
            rounds = np.zeros((B,), np.int32)
            deficit = np.zeros((B,), np.int32)
            prev = np.zeros((B,), np.int32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                rounds[i] = s.spec_rounds
                deficit[i] = s.spec_deficit
                prev[i] = s.spec_prev
            out.update(spec_rounds=rounds, spec_deficit=deficit,
                       spec_prev=prev)
        return out

    def _drain_burst(self, tok_buf, val_buf, logit_buf, *, k: int,
                     paged: bool) -> None:
        """One host sync per burst: fetch the token ring buffer, append
        tokens to their slots, and replay the in-jit done rule (eos /
        max_new / cache exhausted) so the host mirror stays coherent
        with the device's ``active`` flags."""
        bufs = (tok_buf, val_buf) if logit_buf is None \
            else (tok_buf, val_buf, logit_buf)
        with TraceAnnotation("engine.drain", k=k):
            got = jax.device_get(bufs)
        with TraceAnnotation("engine.emit", k=k):
            self._emit_burst(got, k=k, paged=paged)

    def _emit_burst(self, got, *, k: int, paged: bool) -> None:
        """Fold a drained burst's tokens into the slots and stream them."""
        self.n_host_syncs += 1
        toks, valid = got[0], got[1]
        logits = got[2] if len(got) > 2 else None
        n_steps = int(valid.any(axis=1).sum())
        self.n_bursts += 1
        self.n_device_steps += n_steps
        if n_steps < k:
            self.n_burst_early_exits += 1
        fresh: Dict[int, List[int]] = {}
        for kstep in range(n_steps):
            if paged:
                self._count_attn_pages(
                    self._lengths[valid[kstep]] + 1)
            for i, slot in enumerate(self._slots):
                if slot is None or not valid[kstep, i]:
                    continue
                if logits is not None:
                    self.logit_trace.setdefault(slot.rid, []).append(
                        logits[kstep, i].copy())
                slot.tokens.append(int(toks[kstep, i]))
                fresh.setdefault(i, []).append(slot.tokens[-1])
                if paged:
                    self._lengths[i] += 1
                if ((self.eos_id is not None
                     and slot.tokens[-1] == self.eos_id)
                        or len(slot.tokens) >= self.max_new_tokens
                        or (paged
                            and int(self._lengths[i]) >= self.capacity)):
                    slot.done = True
        if not paged:
            self._pos += n_steps
        now = time.monotonic()
        for i, new_toks in fresh.items():
            slot = self._slots[i]
            if slot.t_first is None:
                slot.t_first = now
            if self.stream_cb is not None:
                self.stream_cb(slot.rid, new_toks)

    def _drain_spec_burst(self, tok_buf, val_buf, logit_buf, *,
                          k: int) -> None:
        """Speculative-burst drain: the rings are ``(k, B, spec_k+1)``
        — round ``r`` emitted slot ``b``'s tokens at the valid
        positions, always a contiguous prefix (accepted drafts, then
        one replacement/bonus token, truncated at eos).  Replays the
        in-jit done rule per token and the spec-field update
        (``spec_rounds``/``spec_deficit``/``spec_prev``) per round so
        the host mirror can rebuild device state after any structural
        event, and accumulates the acceptance statistics."""
        bufs = (tok_buf, val_buf) if logit_buf is None \
            else (tok_buf, val_buf, logit_buf)
        with TraceAnnotation("engine.drain", k=k):
            got = jax.device_get(bufs)
        with TraceAnnotation("engine.emit", k=k):
            self._emit_spec_burst(got, k=k)

    def _emit_spec_burst(self, got, *, k: int) -> None:
        """Fold a drained speculative burst into the slots and stream it."""
        self.n_host_syncs += 1
        toks, valid = got[0], got[1]
        logits = got[2] if len(got) > 2 else None
        n_rounds = int(valid.any(axis=(1, 2)).sum())
        self.n_bursts += 1
        self.n_device_steps += n_rounds
        if n_rounds < k:
            self.n_burst_early_exits += 1
        fresh: Dict[int, List[int]] = {}
        for r in range(n_rounds):
            for i, slot in enumerate(self._slots):
                if slot is None or not valid[r, i].any():
                    continue
                # per-round draft budget, recomputed from the
                # *pre-round* host mirrors (same formula as in-jit)
                gb = max(0, min(self.max_new_tokens - len(slot.tokens) - 1,
                                self.capacity - int(self._lengths[i]) - 1,
                                self.spec_k))
                m = int(valid[r, i].sum())
                for j in range(m):
                    if logits is not None:
                        self.logit_trace.setdefault(slot.rid, []).append(
                            logits[r, i, j].copy())
                    slot.tokens.append(int(toks[r, i, j]))
                    fresh.setdefault(i, []).append(slot.tokens[-1])
                    self._lengths[i] += 1
                    if ((self.eos_id is not None
                         and slot.tokens[-1] == self.eos_id)
                            or len(slot.tokens) >= self.max_new_tokens
                            or int(self._lengths[i]) >= self.capacity):
                        slot.done = True
                slot.spec_rounds += 1
                slot.spec_deficit = 1 if m == gb + 1 else 0
                slot.spec_prev = self._seq_tokens(
                    slot, int(self._lengths[i]) - 1,
                    int(self._lengths[i]))[0]
                self.n_spec_rounds += 1
                self.n_spec_tokens += m
                self.n_draft_proposed += gb
                # the round's last emitted token is the replacement /
                # bonus draw, everything before it an accepted draft
                # (a round cut short by an eos *inside* the drafted
                # prefix under-counts by one; the slot finishes then,
                # so the drift is at most 1 per request)
                self.n_draft_accepted += m - 1
                self.spec_accept_hist[min(m - 1, self.spec_k)] += 1
        now = time.monotonic()
        for i, new_toks in fresh.items():
            slot = self._slots[i]
            if slot.t_first is None:
                slot.t_first = now
            if self.stream_cb is not None:
                self.stream_cb(slot.rid, new_toks)

    # -- scheduler internals ------------------------------------------------
    def _expire_queued(self) -> None:
        """Fail queued requests whose TTFT deadline has passed."""
        now = time.monotonic()
        with self._lock:
            dead = self.scheduler.expire(now)
        for req in dead:
            self.n_expired += 1
            self._finish(GenerationResult(
                request_id=req.rid, prompt=req.prompt,
                tokens=np.asarray(req.tokens, np.int32),
                latency_s=now - req.t_submit, status="expired"))

    def _admit(self) -> None:
        self._expire_queued()
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return
        with self._lock:
            if not self.scheduler.pending:
                return
            if self.n_active == 0:
                # batch drained: re-anchor with a fresh prefill wave,
                # taking candidates in lane-priority order
                self._cache = None
                take = list(self.scheduler.candidates())[:len(free)]
                for req in take:
                    self.scheduler.remove(req)
                joins = list(zip(free, take))
                fresh = True
            elif self._pos >= self.capacity:
                # cache exhausted: in-flight slots are about to be
                # truncated; hold newcomers for the fresh re-anchor
                return
            else:
                # mid-decode join: only prompts that fit the current
                # position (scans the whole queue — a long prompt can
                # never block a short one queued behind it)
                joins = []
                for req in self.scheduler.candidates():
                    if len(joins) < len(free) \
                            and req.prompt.shape[0] <= self._pos:
                        self.scheduler.remove(req)
                        joins.append((free[len(joins)], req))
                fresh = False
        if not joins:
            return
        t_admit = time.monotonic()
        B = self.batch_size
        if fresh:
            maxlen = max(req.prompt.shape[0] for _, req in joins)
            self._pos = maxlen
        batch = np.zeros((B, self._pos), np.int32)
        for slot_i, req in joins:
            batch[slot_i, self._pos - req.prompt.shape[0]:] = req.prompt
        logits, cache = self._prefill(self.params, jnp.asarray(batch), None)
        if self._greedy:
            first_np = np.asarray(jnp.argmax(logits, axis=-1)
                                  .astype(jnp.int32))
        else:
            rids = np.zeros((B,), np.int32)
            for slot_i, req in joins:
                rids[slot_i] = req.rid
            first_np = self._sample_rows(logits, rids, np.zeros((B,), np.int32))
        self.n_prefills += 1
        if fresh:
            self._cache = cache
        else:
            slot_ids = [slot_i for slot_i, _ in joins]
            self._cache = self._splice_cache(self._cache, cache, slot_ids)
            self.n_joins += len(joins)
        logits_np = np.asarray(logits) if self.trace_logits else None
        now = time.monotonic()
        for slot_i, req in joins:
            if self.trace_logits:
                self.logit_trace.setdefault(req.rid, []).append(
                    logits_np[slot_i].copy())
            slot = _Slot(req, first_np[slot_i], self.eos_id,
                         self.max_new_tokens, t_admit)
            slot.t_first = now
            slot.adm_seq = self._adm_seq
            self._adm_seq += 1
            self._slots[slot_i] = slot
            if self.stream_cb is not None:
                self.stream_cb(slot.rid, [slot.tokens[-1]])
        self._dev.mark_dirty()

    def _evict(self) -> List[GenerationResult]:
        out: List[GenerationResult] = []
        now = time.monotonic()
        for i, slot in enumerate(self._slots):
            if slot is None or not slot.done:
                continue
            res = self._make_result(slot, now)
            out.append(res)
            self._finish(res)
            self._slots[i] = None
            self.n_evictions += 1
        return out

    # -- paged scheduler ----------------------------------------------------
    def _step_paged(self) -> List[GenerationResult]:
        """One engine tick in paged mode.

        While any slot is still consuming its prompt, one batched
        *mixed* megastep advances every busy slot: decoding slots feed
        their last token (t_valid=1), prefilling slots feed their next
        ``prefill_chunk`` prompt tokens, idle slots ride along masked
        out (t_valid=0).  Once the batch is pure decode, the engine
        runs *bursts* instead: up to ``burst`` fused device steps per
        host round-trip (K=1 whenever requests are queued, so the next
        eviction admits immediately).  T therefore buckets to just two
        shapes — 1 (burst body) and ``prefill_chunk`` — and the burst
        bound is traced, so each megastep compiles exactly once.
        Before any step, shared blocks in the coming write range are
        forked (COW) and page tables pre-extended to cover it; after
        it, newly completed pages are published to the content table
        for future joiners.
        """
        # periodic retention sweep: TTL expiry must not depend on
        # allocation traffic — an idle server still ticks through here,
        # so expired prefix blocks are retired even with no admissions
        # or completions in flight (no-op without retain_ttl_s)
        with TraceAnnotation("engine.admit"):
            self.allocator.sweep()
            self._admit_paged()
        with TraceAnnotation("engine.evict"):
            finished = self._evict_paged()
        busy = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        if not busy:
            return finished
        self._ensure_paged_cache()
        if any(s.prefill_off < len(s.prompt) for _, s in busy):
            self._step_paged_mixed(busy)
        else:
            self._step_paged_burst(busy)
        if self.share_prefix:
            for i, slot in busy:
                self._register_full_pages(i, slot)
        with TraceAnnotation("engine.evict"):
            return finished + self._evict_paged()

    def _step_paged_mixed(self, busy) -> None:
        """One mixed prefill+decode megastep (T = ``prefill_chunk``)."""
        T = self.prefill_chunk
        with TraceAnnotation("engine.prepare", t=T):
            staged = self._stage_mixed(busy, T)
        if staged is None:
            return
        tokens, t_valid, emit, st = staged
        with TraceAnnotation("engine.dispatch", t=T):
            if self._spec:
                cache, dcache, st, sampled, logits = self._mixed_fn(
                    self.params, self.draft_params, self._paged_cache,
                    self._draft_cache, st, jnp.asarray(tokens),
                    jnp.asarray(t_valid), jnp.asarray(emit))
                self._draft_cache = dcache
            else:
                cache, st, sampled, logits = self._mixed_fn(
                    self.params, self._paged_cache, st, jnp.asarray(tokens),
                    jnp.asarray(t_valid), jnp.asarray(emit))
        self._paged_cache = cache
        self._dev.adopt(st)
        self.n_prefill_chunks += 1
        self.n_device_steps += 1
        self._count_attn_pages(self._lengths[t_valid > 0]
                               + t_valid[t_valid > 0])
        with TraceAnnotation("engine.drain", t=T):
            if self.trace_logits:
                sampled_np, logits_np = jax.device_get((sampled, logits))
            else:
                sampled_np, logits_np = np.asarray(sampled), None
        self.n_host_syncs += 1
        with TraceAnnotation("engine.emit", t=T):
            self._emit_mixed(busy, tokens, t_valid, sampled_np, logits_np)

    def _stage_mixed(self, busy, T: int):
        """The mixed step's inputs, with COW forks and page extensions
        done and the slot state on the device; None when no slot has
        work."""
        tokens = np.zeros((self.batch_size, T), np.int32)
        t_valid = np.zeros((self.batch_size,), np.int32)
        emit = np.zeros((self.batch_size,), bool)
        for i, slot in busy:
            if slot.done:
                continue
            if slot.prefill_off < len(slot.prompt):
                n = min(T, len(slot.prompt) - slot.prefill_off)
                tokens[i, :n] = slot.prompt[slot.prefill_off:
                                            slot.prefill_off + n]
                t_valid[i] = n
                emit[i] = slot.prefill_off + n >= len(slot.prompt)
            elif self._lengths[i] >= self.capacity:
                slot.done = True      # cache strip exhausted: truncate
            else:
                tokens[i, 0] = slot.tokens[-1]
                t_valid[i] = 1
                emit[i] = True
        if not t_valid.any():
            return None
        for i, slot in busy:
            if t_valid[i]:
                self._cow_write_range(i, slot, int(self._lengths[i]),
                                      int(t_valid[i]))
                self._extend_blocks(i, slot,
                                    int(self._lengths[i]) + int(t_valid[i]))
        return tokens, t_valid, emit, self._dev.device(self._paged_state)

    def _emit_mixed(self, busy, tokens, t_valid, sampled_np,
                    logits_np) -> None:
        """Advance the slots by what the mixed step consumed, and hand
        each slot that finished its prompt or decoded its token."""
        for i, slot in busy:
            if not t_valid[i]:
                continue
            was_prefilling = slot.prefill_off < len(slot.prompt)
            self._lengths[i] += t_valid[i]
            if self._spec:
                # replay of the in-jit spec-field update: consuming any
                # chunk catches the draft cache up (deficit 0) and the
                # chunk's last token sits at position lengths-1
                slot.spec_deficit = 0
                slot.spec_prev = int(tokens[i, int(t_valid[i]) - 1])
            if was_prefilling:
                slot.prefill_off += int(t_valid[i])
                if slot.prefill_off < len(slot.prompt):
                    continue          # more chunks to go; no token yet
                self.n_prefills += 1
            if self.trace_logits:
                self.logit_trace.setdefault(slot.rid, []).append(
                    logits_np[i].copy())
            slot.tokens.append(int(sampled_np[i]))
            if slot.t_first is None:
                slot.t_first = time.monotonic()
            if self.stream_cb is not None:
                self.stream_cb(slot.rid, [slot.tokens[-1]])
            # replay of the megastep's in-jit done rule
            if ((self.eos_id is not None and slot.tokens[-1] == self.eos_id)
                    or len(slot.tokens) >= self.max_new_tokens
                    or int(self._lengths[i]) >= self.capacity):
                slot.done = True

    def _step_paged_burst(self, busy) -> None:
        """Up to ``burst`` pure-decode megasteps in one device loop.

        Before launching, every active slot's page table is extended to
        cover the burst's worst-case write range (drawn from the
        admission-time reservation, so this can never fail) and any
        shared block in that range is COW-forked — the loop then never
        needs the host until its ring buffer is drained."""
        with self._lock:
            pending = self.scheduler.pending
        k = 1 if pending else min(self.burst, self.max_burst)
        k = max(1, k)
        with TraceAnnotation("engine.prepare", k=k):
            st = self._stage_burst(busy, k)
        if st is None:
            return
        with TraceAnnotation("engine.dispatch", k=k):
            if self._spec:
                out = self._burst_fn(self.params, self.draft_params,
                                     self._paged_cache, self._draft_cache,
                                     st, np.int32(k))
            else:
                out = self._burst_fn(self.params, self._paged_cache, st,
                                     np.int32(k))
        if self._spec:
            self._paged_cache, self._draft_cache = out[0], out[1]
            self._dev.adopt(out[2])
            self._drain_spec_burst(out[3], out[4],
                                   out[5] if self.trace_logits else None,
                                   k=k)
            return
        self._paged_cache = out[0]
        self._dev.adopt(out[1])
        self._drain_burst(out[2], out[3],
                          out[4] if self.trace_logits else None,
                          k=k, paged=True)

    def _stage_burst(self, busy, k: int):
        """Extend and COW-fork every active slot's pages over the
        burst's write range; the slot state on the device, or None when
        no slot is active."""
        any_active = False
        for i, slot in busy:
            if slot.done:
                continue
            L = int(self._lengths[i])
            if L >= self.capacity:
                slot.done = True      # cache strip exhausted: truncate
                continue
            # a plain burst writes at most k tokens; a speculative one
            # writes up to spec_k+1 positions per round (even rejected
            # drafts are written, then rolled back by arithmetic).
            # Both stop at max_new (final length = prompt + max_new - 1,
            # and the per-round draft budget keeps every *write* under
            # that too) and at capacity.
            span = (self.spec_k + 1) if self._spec else 1
            target = min(L + k * span,
                         len(slot.prompt) + self.max_new_tokens - 1,
                         self.capacity)
            if target > L:
                self._cow_write_range(i, slot, L, target - L)
                self._extend_blocks(i, slot, target)
            any_active = True
        if not any_active:
            return None
        return self._dev.device(self._paged_state)

    def _match_prefix(self, prompt: np.ndarray) \
            -> Tuple[List[int], List[bytes], int]:
        """Longest resident chain matching the prompt.

        Returns ``(mapped, digests, matched)``: physical blocks to map
        at pages ``0..len(mapped)-1``, chain digests of the pages fully
        covered by ``matched``, and the number of prompt tokens those
        blocks serve.  Matching walks full pages by chain digest, then
        tries to land the final partial page on another sequence's
        completed block (``lookup_tail``).  ``matched`` is capped at
        ``len(prompt) - 1`` so at least one prompt token always runs
        through the model — the joiner's first sampled token needs
        logits — which may leave the write cursor inside a shared block;
        the COW fork at write time keeps that sound.
        """
        if not self.share_prefix:
            return [], [], 0
        bs = self.block_size
        L = len(prompt)
        parent = ROOT_DIGEST
        mapped: List[int] = []
        digests: List[bytes] = []
        off = 0
        while off + bs <= L:
            toks = tuple(int(t) for t in prompt[off:off + bs])
            block = self.allocator.lookup(parent, toks)
            if block is None:
                break
            parent = chain_digest(parent, toks)
            mapped.append(block)
            digests.append(parent)
            off += bs
        if 2 <= L - off < bs:
            # a 1-token tail is pure overhead: its only token would be
            # re-run (and fork the block) anyway, so require >= 2
            tail = self.allocator.lookup_tail(
                parent, tuple(int(t) for t in prompt[off:L]))
            if tail is not None:
                mapped.append(tail)
                off = L
        matched = min(off, L - 1)
        return mapped, digests[:matched // bs], matched

    def _match_prefix_cached(self, req: SchedRequest):
        """Memoized match for a queued request.  Blocks only enter or
        leave the content table through register/unregister, each of
        which bumps the allocator's ``epoch`` — so while the epoch is
        unchanged a cached match is still valid and a blocked request
        costs O(1) per admission scan instead of re-hashing its whole
        prompt."""
        if req.match is None or req.match_epoch != self.allocator.epoch:
            req.match = self._match_prefix(req.prompt)
            req.match_epoch = self.allocator.epoch
        return req.match

    def _admit_paged(self) -> None:
        """Admit queued requests into free slots, in lane-priority order
        (interactive first, FIFO within a lane).

        A request needs a slot plus a worst-case *private*-block
        reservation: the pages its matched prefix shares forever are
        discounted, everything else (fresh prompt pages, decode
        extensions, possible COW forks in the write range) is budgeted
        up front, so mid-decode allocation never fails.  Recurrent
        families additionally need one free state slab — checked before
        anything is taken, so admission stays all-or-nothing across
        both pools.  The scan is *size-aware*: a candidate that does
        not fit stays queued and the scan moves on, so a too-large
        request can never head-of-line-block a smaller one behind it.
        If an interactive candidate is blocked on resources while
        batch-lane slots are running, the youngest batch slot is
        preempted (spilled to host memory, re-queued at its lane's
        front) and the scan retries."""
        self._expire_queued()
        while True:
            blocked_interactive = self._admit_paged_scan()
            if blocked_interactive and self._preempt_for_interactive():
                continue
            return

    def _admit_paged_scan(self) -> bool:
        """One admission pass; returns True if an interactive candidate
        was left queued for lack of resources."""
        free = [i for i, s in enumerate(self._slots) if s is None]
        mid_decode = self.n_active > 0
        joins = []
        blocked_interactive = False
        with self._lock:
            for req in self.scheduler.candidates():
                if blocked_interactive and req.lane == "batch":
                    # strict priority: batch work must not slip past a
                    # resource-blocked interactive candidate (it would
                    # be preempted right back — livelock)
                    continue
                if not free:
                    if req.lane == "interactive":
                        blocked_interactive = True
                    break
                try:
                    fit = self._restore_fit(req, free) if req.preempted \
                        else self._fresh_fit(req, free)
                except CacheFullError:
                    # transient allocator storm (real or injected): the
                    # candidate stays queued, never oom-failed
                    continue
                except Exception as exc:
                    # attributable to this candidate alone: fail it,
                    # keep scanning — one bad request must not block
                    # the queue or poison its neighbours
                    self.scheduler.remove(req)
                    self._finish(GenerationResult(
                        request_id=req.rid, prompt=req.prompt,
                        tokens=np.asarray(req.tokens, np.int32),
                        latency_s=time.monotonic() - req.t_submit,
                        status="error", error=f"admission failed: {exc}"))
                    continue
                if fit is None:
                    if self.allocator.n_live == 0 and self._reserved == 0 \
                            and (self.state_store is None
                                 or self.state_store.n_live == 0):
                        # does not fit an *empty* pool: it never will —
                        # fail it instead of wedging the queue forever
                        self.scheduler.remove(req)
                        self._finish(GenerationResult(
                            request_id=req.rid, prompt=req.prompt,
                            tokens=np.asarray(req.tokens, np.int32),
                            latency_s=time.monotonic() - req.t_submit,
                            status="oom"))
                        continue
                    if req.lane == "interactive":
                        blocked_interactive = True
                    continue           # size-aware: scan past this one
                self.scheduler.remove(req)
                joins.append(fit)
        for join in joins:
            kind, slot_i, req = join[0], join[1], join[2]
            slot = self._build_restore_slot(join) if kind == "restore" \
                else self._build_fresh_slot(join, mid_decode)
            slot.adm_seq = self._adm_seq
            self._adm_seq += 1
            self._slots[slot_i] = slot
        if joins:
            self._dev.mark_dirty()
        return blocked_interactive

    def _fresh_fit(self, req: SchedRequest, free: List[int]):
        """Try to take resources for a fresh admission (all-or-nothing);
        None if the request does not fit right now."""
        f = self.fault_plan.fire("admit") if self.fault_plan else None
        if f is not None and f.action == "raise":
            raise f.make_exc()         # before anything is taken
        plen = req.prompt.shape[0]
        mapped, digests, matched = self._match_prefix_cached(req)
        total = self.allocator.blocks_for(
            min(plen + self.max_new_tokens, self.capacity))
        # pages below matched // block_size are never written by this
        # slot, so they stay shared for its whole lifetime
        needed = total - matched // self.block_size
        # retained mapped blocks are resurrected off the free list by
        # share() below — they consume free-list entries on top of the
        # private budget, so the fit check must count them
        n_resurrect = sum(1 for b in mapped if self.allocator.ref(b) == 0)
        if needed + n_resurrect > self.allocator.n_free - self._reserved:
            return None
        if self.state_store is not None and self.state_store.n_free == 0:
            return None                # state slabs exhausted: stay queued
        # share (and resurrect) the mapped prefix *before* acquiring
        # fresh blocks — acquire recycles retained blocks and must never
        # recycle one this very admission is about to map
        self.allocator.share(mapped)
        n_fresh = self.allocator.blocks_for(plen) - len(mapped)
        try:
            fresh = self.allocator.acquire(n_fresh)
        except CacheFullError:           # unreachable given the check above
            self.allocator.release(mapped)
            return None
        blocks = mapped + fresh
        self._reserved += needed - n_fresh
        slab = 0
        if self.state_store is not None:
            slab = self.state_store.admit(req.rid)
            # the slab's previous state is zeroed by the model's first
            # step for this slot (lengths == 0 blanking)
            self.state_store.mark_reset(slab)
        return ("fresh", free.pop(0), req, blocks, needed - n_fresh,
                matched, digests, slab)

    def _build_fresh_slot(self, join, mid_decode: bool) -> "_PagedSlot":
        _, slot_i, req, blocks, reserve, matched, digests, slab = join
        if mid_decode:
            self.n_joins += 1
        if matched:
            self.n_prefix_hits += 1
            self.n_shared_tokens += matched
        slot = _PagedSlot(req, blocks, reserve, prefill_off=matched,
                          digests=list(digests))
        self._page_table[slot_i, :] = 0
        self._page_table[slot_i, :len(blocks)] = blocks
        self._lengths[slot_i] = matched
        self._state_slots[slot_i] = slab
        return slot

    def _restore_fit(self, req: SchedRequest, free: List[int]):
        """Try to take resources to re-admit a preempted request.  No
        prefix-share discount: every page is acquired private and the
        spilled KV/state is scattered back, so the restored slot is
        bit-identical to never having been preempted."""
        plen = req.prompt.shape[0]
        total = self.allocator.blocks_for(
            min(plen + self.max_new_tokens, self.capacity))
        if total > self.allocator.n_free - self._reserved:
            return None
        if self.state_store is not None and self.state_store.n_free == 0:
            return None
        n_now = self.allocator.blocks_for(max(req.length, 1))
        blocks = self.allocator.acquire(n_now)
        self._reserved += total - n_now
        slab = 0
        if self.state_store is not None:
            slab = self.state_store.admit(req.rid)
            self.state_store.mark_reset(slab)   # scatter overwrites it
        return ("restore", free.pop(0), req, blocks, total - n_now, slab)

    def _build_restore_slot(self, join) -> "_PagedSlot":
        """Scatter a preempted request's spilled pages/slab into its new
        physical homes and rebuild the slot mid-sequence.  Attention
        reads go through the page table and sampling keys are a pure
        function of (request, step), so decode resumes bit-identically
        regardless of where the pages landed."""
        _, slot_i, req, blocks, reserve, slab = join
        self._ensure_paged_cache()
        if req.spill is not None:
            spill = req.spill["target"] if self._spec else req.spill
            self._paged_cache = self._scatter_pages(
                self._paged_cache, spill,
                jnp.asarray(blocks, jnp.int32), jnp.int32(slab))
            if self._spec:
                self._draft_cache = self._scatter_draft(
                    self._draft_cache, req.spill["draft"],
                    jnp.asarray(blocks, jnp.int32), jnp.int32(0))
        slot = _PagedSlot(req, blocks, reserve,
                          prefill_off=len(req.prompt),
                          digests=list(req.digests))
        slot.tokens = list(req.tokens)
        if self._spec and req.spec is not None:
            slot.spec_rounds = int(req.spec["rounds"])
            slot.spec_deficit = int(req.spec["deficit"])
            slot.spec_prev = int(req.spec["prev"])
        self._page_table[slot_i, :] = 0
        self._page_table[slot_i, :len(blocks)] = blocks
        self._lengths[slot_i] = req.length
        self._state_slots[slot_i] = slab
        self.n_restores += 1
        if self.n_active > 0:
            self.n_joins += 1
        return slot

    def _paged_cache_shardings(self):
        """NamedSharding pytree for the paged pool (mesh mode only):
        block/slot axes replicated, feature dims on "model"."""
        from jax.sharding import NamedSharding
        from ..models.sharding import paged_cache_specs
        kw = self._paged_cache_kwargs()
        struct = jax.eval_shape(
            lambda: self.model.init_paged_cache(
                self.allocator.num_blocks, self.block_size,
                dtype=self.cache_dtype, **kw))
        axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        specs = paged_cache_specs(struct, axis_sizes=axis_sizes)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _paged_cache_kwargs(self):
        """Keyword args for ``model.init_paged_cache`` beyond the block
        geometry: state-slab provisioning, and the int8 switch."""
        kw = {"num_state_slots": self.num_state_slots} \
            if self.state_store is not None else {}
        if self._quant:
            kw["kv_dtype"] = "int8"
        return kw

    def _ensure_paged_cache(self) -> None:
        if self._paged_cache is None:
            kw = self._paged_cache_kwargs()
            shardings = None
            if self.mesh is not None:
                shardings = self._paged_cache_shardings()
                sig = inspect.signature(self.model.init_paged_cache)
                if "shardings" in sig.parameters:
                    kw["shardings"], shardings = shardings, None
            cache = self.model.init_paged_cache(
                self.allocator.num_blocks, self.block_size,
                dtype=self.cache_dtype, **kw)
            if shardings is not None:   # model without creation-time placement
                cache = jax.device_put(cache, shardings)
            self._paged_cache = cache
        if self._spec and self._draft_cache is None:
            # the draft pool shadows the target pool one-to-one: same
            # block count / block size / page tables, draft-model dims
            self._draft_cache = self.draft_model.init_paged_cache(
                self.allocator.num_blocks, self.block_size,
                dtype=self.cache_dtype)

    # -- preemption ---------------------------------------------------------
    def preempt(self, rid: int) -> bool:
        """Spill the slot serving ``rid`` to host memory and re-queue it
        at the front of its lane (operator / test hook; the scheduler
        calls the same path automatically for blocked interactive
        work).  Returns False if ``rid`` is not in a slot."""
        if not self.paged:
            raise ValueError("preemption requires paged mode")
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.rid == rid and not slot.done:
                with self._sharding_ctx():
                    self._preempt_slot(i)
                return True
        return False

    def _preempt_for_interactive(self) -> bool:
        """Spill the youngest running batch-lane slot (least cached work
        lost) to make room for a blocked interactive candidate."""
        victims = [(slot.adm_seq, i)
                   for i, slot in enumerate(self._slots)
                   if slot is not None and slot.lane == "batch"
                   and not slot.done
                   and (self._gather_pages is not None
                        or slot.prefill_off < len(slot.prompt)
                        or not slot.tokens)]
        if not victims:
            return False
        self._preempt_slot(max(victims)[1])
        return True

    def _preempt_slot(self, slot_i: int) -> None:
        """Evict slot ``slot_i`` mid-flight, keeping its work: decode
        slots get their used KV pages (and recurrent state slab)
        gathered to host memory for a bit-identical restore; a slot
        still mid-prefill (no token emitted yet) is simply restarted —
        re-prefilling is deterministic, so nothing observable is lost.
        The request re-enters the *front* of its lane."""
        slot = self._slots[slot_i]
        req = SchedRequest(rid=slot.rid, prompt=slot.prompt, lane=slot.lane,
                           deadline=slot.deadline, tag=slot.tag,
                           t_submit=slot.t_submit, t_admit=slot.t_admit,
                           t_first=slot.t_first)
        if slot.tokens and slot.prefill_off >= len(slot.prompt):
            if self._gather_pages is None:
                raise RuntimeError(
                    f"{type(self.model).__name__} has recurrent state but "
                    "no gather_paged_pages/scatter_paged_pages: cannot "
                    "preempt a decoding slot")
            L = int(self._lengths[slot_i])
            n_pages = self.allocator.blocks_for(L)
            payload = self._gather_pages(
                self._paged_cache,
                jnp.asarray(slot.blocks[:n_pages], jnp.int32),
                jnp.int32(self._state_slots[slot_i]))
            if self._spec:
                # spill the draft pool's view of the same pages, plus
                # the spec mirrors, so restore resumes the identical
                # draft state and PRNG stream
                dpayload = self._gather_draft(
                    self._draft_cache,
                    jnp.asarray(slot.blocks[:n_pages], jnp.int32),
                    jnp.int32(0))
                req.spill = {"target": jax.device_get(payload),
                             "draft": jax.device_get(dpayload)}
                req.spec = {"rounds": slot.spec_rounds,
                            "deficit": slot.spec_deficit,
                            "prev": slot.spec_prev}
            else:
                req.spill = jax.device_get(payload)
            req.length = L
            req.tokens = list(slot.tokens)
            req.digests = list(slot.digests)
        self.allocator.release(slot.blocks)
        if self.state_store is not None:
            self.state_store.evict(slot.rid)
        self._reserved -= slot.reserve_left
        self._page_table[slot_i, :] = 0
        self._lengths[slot_i] = 0
        self._slots[slot_i] = None
        self._dev.mark_dirty()
        self.n_preemptions += 1
        with self._lock:
            self.scheduler.push(req, front=True)

    def _extend_blocks(self, slot_i: int, slot: _PagedSlot,
                       n_tokens: int) -> None:
        """Grow a slot's page list to cover ``n_tokens`` cached tokens,
        drawing on its admission-time reservation (never fails)."""
        need = -(-n_tokens // self.block_size)
        while len(slot.blocks) < need:
            assert slot.reserve_left > 0, "reservation under-counted"
            (bid,) = self.allocator.acquire(1)
            slot.blocks.append(bid)
            slot.reserve_left -= 1
            self._reserved -= 1
            self._page_table[slot_i, len(slot.blocks) - 1] = bid
            self._dev.mark_dirty()

    def _cow_write_range(self, slot_i: int, slot: _PagedSlot, start: int,
                         n_new: int) -> None:
        """Copy-on-write: fork every *shared* block in the page range
        the coming ``paged_scatter`` will touch, so the write can never
        leak into another slot's view of the pool."""
        bs = self.block_size
        first = start // bs
        last = (start + n_new - 1) // bs
        for p in range(first, min(last + 1, len(slot.blocks))):
            # fork if shared — or still registered: a resurrected block
            # can be held at refcount 1, but the content table still
            # advertises its KV, so writing in place would corrupt what
            # future joiners map
            if self.allocator.ref(slot.blocks[p]) > 1 \
                    or self.allocator.is_registered(slot.blocks[p]):
                self._fork_block(slot_i, slot, p)

    def _fork_block(self, slot_i: int, slot: _PagedSlot, p: int) -> None:
        """Give the slot a private copy of page ``p``: acquire a block
        from the slot's reservation, copy the page's KV across every
        layer, swap the page-table entry, and drop our reference to the
        shared original (its other holders keep it alive)."""
        old = slot.blocks[p]
        assert slot.reserve_left > 0, "COW fork not covered by reservation"
        (new,) = self.allocator.acquire(1)
        slot.reserve_left -= 1
        self._reserved -= 1
        self._paged_cache = self._copy_block(self._paged_cache, old, new)
        self.allocator.release([old])
        slot.blocks[p] = new
        self._page_table[slot_i, p] = new
        self._dev.mark_dirty()
        self.n_cow_forks += 1

    def _seq_tokens(self, slot: _PagedSlot, start: int,
                    stop: int) -> Tuple[int, ...]:
        """Tokens at cache positions [start, stop): prompt, then the
        generated stream (token ``g`` was written at ``len(prompt)+g``)."""
        L = len(slot.prompt)
        return tuple(int(slot.prompt[p]) if p < L
                     else int(slot.tokens[p - L])
                     for p in range(start, stop))

    def _register_full_pages(self, slot_i: int, slot: _PagedSlot) -> None:
        """Publish every newly completed page to the content table so
        later joiners can map it instead of re-prefilling."""
        bs = self.block_size
        length = int(self._lengths[slot_i])
        while (len(slot.digests) + 1) * bs <= length:
            p = len(slot.digests)
            toks = self._seq_tokens(slot, p * bs, (p + 1) * bs)
            parent = slot.digests[-1] if slot.digests else ROOT_DIGEST
            self.allocator.register(slot.blocks[p], parent, toks)
            slot.digests.append(chain_digest(parent, toks))

    def _evict_paged(self) -> List[GenerationResult]:
        out: List[GenerationResult] = []
        now = time.monotonic()
        for i, slot in enumerate(self._slots):
            if slot is None or not slot.done:
                continue
            res = self._make_result(slot, now)
            out.append(res)
            self._finish(res)
            # refcounted release: shared blocks stay resident (and
            # content-addressable) as long as any other slot maps them;
            # registered blocks at refcount 0 are *retained* — the next
            # identical prompt maps them instead of re-prefilling
            self.allocator.release(slot.blocks)
            if self.state_store is not None:
                self.state_store.evict(slot.rid)
            self._reserved -= slot.reserve_left
            self._page_table[i, :] = 0
            self._lengths[i] = 0
            self._slots[i] = None
            self._dev.mark_dirty()
            self.n_evictions += 1
        return out

    # -- cache splicing -----------------------------------------------------
    def _discover_batch_axes(self, seq_len: int):
        """Which axis of each cache leaf is the batch axis?  Compare
        cache shapes for batch B vs B+1 (eval_shape: no compilation)."""
        def shapes(batch):
            tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
            return jax.eval_shape(self._prefill, self.params, tokens, None)[1]

        def axis(a, b):
            for i, (p, q) in enumerate(zip(a.shape, b.shape)):
                if p != q:
                    return i
            return -1  # leaf independent of batch
        return jax.tree.map(axis, shapes(self.batch_size),
                            shapes(self.batch_size + 1))

    def _splice_cache(self, live, fresh, slot_ids: List[int]):
        if self._batch_axes is None:
            self._batch_axes = self._discover_batch_axes(max(self._pos, 1))
        sel = jnp.asarray(slot_ids, jnp.int32)

        def merge(old, new, ax):
            if ax < 0:
                return old
            idx = [slice(None)] * old.ndim
            idx[ax] = sel
            return old.at[tuple(idx)].set(new[tuple(idx)])
        return jax.tree.map(merge, live, fresh, self._batch_axes)


def _generic_copy_paged_block(cache, src: int, dst: int):
    """Fallback COW copy for models without ``copy_paged_block``: every
    paged-cache leaf is a ``(num_blocks, block_size, ...)`` store,
    optionally stacked under a leading scan-over-layers axis, so the
    block axis is ``ndim - 4``."""
    def cp(leaf):
        idx = [slice(None)] * (leaf.ndim - 4)
        return leaf.at[tuple(idx + [dst])].set(leaf[tuple(idx + [src])])
    return jax.tree.map(cp, cache)


def _generic_gather_pages(cache, blocks, slab):
    """Fallback spill gather for attn-only models without
    ``gather_paged_pages`` (same block-axis convention as the COW
    fallback; ``slab`` is unused — recurrent stacks must implement the
    model-level protocol)."""
    del slab

    def take(leaf):
        idx = [slice(None)] * (leaf.ndim - 4)
        return leaf[tuple(idx + [blocks])]
    return jax.tree.map(take, cache)


def _generic_scatter_pages(cache, payload, blocks, slab):
    """Fallback spill scatter for attn-only models (inverse of
    ``_generic_gather_pages``)."""
    del slab

    def put(leaf, p):
        idx = [slice(None)] * (leaf.ndim - 4)
        return leaf.at[tuple(idx + [blocks])].set(p)
    return jax.tree.map(put, cache, payload)
