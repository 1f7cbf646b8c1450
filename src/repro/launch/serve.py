"""Serving launcher: continuous batching through the stream pipeline.

Requests are pushed into an appsrc, micro-batched by ``tensor_batcher``
(rate-adaptive: full batch or ``max_wait_ms``, whichever first), run
through the continuous-batching ServeEngine mounted as a
``tensor_filter``, and split back into per-request results by
``tensor_unbatcher``.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
        --requests 8 --batch 4 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --smoke --direct  # no pipeline
"""
from __future__ import annotations

import argparse
import threading
import time
from typing import List, Optional

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..models import build_model
from ..models.config import ModelConfig, SSMConfig
from ..serving import ServeEngine
from .compile_cache import enable_compile_cache

# demo-scale config per serving family (mirrors the conformance matrix
# in tests/conftest.py): --family serves any of them through the same
# paged engine — attention layers page, recurrent layers use state slabs
_FAM_BASE = ModelConfig(
    arch_id="fam-demo", family="dense", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
    norm="rmsnorm", mlp_act="swiglu", rope="rope",
    param_dtype="float32", compute_dtype="float32")
_FAM_SSM = SSMConfig(d_state=16, d_conv=4, expand=2)
FAMILY_CONFIGS = {
    "transformer": _FAM_BASE,
    "mamba": _FAM_BASE.replace(arch_id="fam-mamba", family="hybrid",
                               ssm=_FAM_SSM, attn_layer_period=1,
                               attn_layer_offset=1),
    "xlstm": _FAM_BASE.replace(arch_id="fam-xlstm", family="ssm", d_ff=0,
                               n_kv_heads=4, rope="none",
                               ssm=SSMConfig(d_state=16, d_conv=4, expand=2,
                                             slstm_every=2)),
    "hybrid": _FAM_BASE.replace(arch_id="fam-hybrid", family="hybrid",
                                ssm=_FAM_SSM, attn_layer_period=2,
                                attn_layer_offset=0),
}


def _print_spec_stats(engine):
    ls = engine.loop_stats()
    if "n_spec_rounds" not in ls:
        return
    rounds = max(1, ls["n_spec_rounds"])
    print(f"speculative: K={ls['spec_k']}, {ls['n_spec_rounds']} rounds -> "
          f"{ls['n_spec_tokens']} tokens "
          f"({ls['n_spec_tokens'] / rounds:.2f}/round), accept rate "
          f"{ls['spec_accept_rate']:.2f}, hist {ls['spec_accept_hist']}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-360m")
    ap.add_argument("--family", choices=["arch"] + sorted(FAMILY_CONFIGS),
                    default="arch",
                    help="serve a demo model of this family (transformer/"
                         "mamba/xlstm/hybrid) instead of --arch; recurrent "
                         "families run paged via per-slot state slabs")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=50.0)
    ap.add_argument("--direct", action="store_true",
                    help="call engine.serve() directly instead of the pipeline")
    ap.add_argument("--paged", choices=["auto", "on", "off"], default="auto",
                    help="block-paged KV cache (auto: on when the model "
                         "supports it)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged mode: tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged mode: pool size (default: batch*capacity "
                         "worth of blocks)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="paged mode: prompt tokens cached per join step")
    ap.add_argument("--share-prefix", choices=["auto", "on", "off"],
                    default="auto",
                    help="paged mode: map requests' common prompt prefixes "
                         "onto already-resident KV blocks (copy-on-write; "
                         "auto: on whenever paged)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy decode; > 0 samples from "
                         "softmax(logits / temperature)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="restrict sampling to the k highest logits")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed (per-request, per-step keys "
                         "are derived from it — identical across modes)")
    ap.add_argument("--shared-prompt", type=int, default=0,
                    help="give every request this many identical leading "
                         "prompt tokens (exercises prefix sharing)")
    ap.add_argument("--num-state-slots", type=int, default=None,
                    help="recurrent families: state slabs in the pool "
                         "(default: one per batch slot; fewer gates "
                         "admission like a small block pool)")
    ap.add_argument("--listen", type=int, default=None, metavar="PORT",
                    help="serve over TCP via the tensor_query elements "
                         "(0 = ephemeral port).  With --smoke, drives the "
                         "synthetic requests through a loopback client and "
                         "exits; otherwise serves until interrupted")
    ap.add_argument("--lanes", default="interactive",
                    help="comma list of priority lanes the smoke client "
                         "cycles through (e.g. 'interactive,batch'; batch "
                         "lane requests are preemptible)")
    ap.add_argument("--max-wait-ms-net", type=float, default=5.0,
                    help="--listen: micro-batch window of the server-side "
                         "tensor_batcher")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0,
                    help="--listen (standing server): on SIGTERM/SIGINT, "
                         "stop admitting and give in-flight requests this "
                         "long to finish before cancelling them; every "
                         "client gets a terminal frame and the process "
                         "exits 0")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="serve tensor-parallel over the first N devices "
                         "(a (1, N) data×model mesh; paged mode only). "
                         "Weights shard by the training PartitionSpec "
                         "rules, the paged KV pool shards head_dim, and "
                         "decode output is token-identical to N=1. "
                         "On CPU, simulate devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--retain-cap", type=int, default=None,
                    help="paged mode: cap on retained (prefix-reusable) "
                         "free blocks; the oldest are retired beyond it "
                         "(default: unbounded)")
    ap.add_argument("--retain-ttl-s", type=float, default=None,
                    help="paged mode: retire retained blocks older than "
                         "this many seconds (default: no TTL)")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8"],
                    default=None,
                    help="KV cache storage precision (default: engine "
                         "default, f32).  'int8' block-quantizes the paged "
                         "pool with per-row scales — ~3-4x the resident "
                         "requests at equal pool bytes, greedy-token drift "
                         "bounded by the drift-tolerance suite (paged mode "
                         "only; incompatible with --mesh and --spec-k)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed and "
                         "verified per burst round (0 = off; paged "
                         "transformer-family targets only — recurrent "
                         "state cannot roll back rejected tokens)")
    ap.add_argument("--draft-config", default=None, metavar="ARCH",
                    help="--spec-k: the draft model — an --arch id sharing "
                         "the target's vocabulary, or 'tiny' for an "
                         "auto-shrunken copy of the target config (the "
                         "default when --spec-k > 0)")
    ap.add_argument("--burst", type=int, default=8,
                    help="decode burst length K: fused device steps per "
                         "host round-trip when no admissions/prefills are "
                         "pending (1 = drain every token; the engine "
                         "degrades to 1 itself whenever the queue is "
                         "non-empty, so join latency is unchanged)")
    return ap


_RECURRENT_FAMILIES = ("mamba", "xlstm", "hybrid")


def validate_args(args) -> None:
    """Fail fast on flag combinations the engine would reject anyway —
    but deep inside construction, after weights are already built.  Each
    check is a one-line error naming both offending flags, raised before
    any model work starts."""
    if args.requests < 1:
        raise SystemExit("--requests must be >= 1")
    if args.shared_prompt >= args.prompt_len - 1:
        # the unique suffix needs at least one token of length spread
        raise SystemExit("--shared-prompt must be < --prompt-len - 1")
    if args.spec_k > 0:
        if args.mesh is not None:
            raise SystemExit(
                "--spec-k and --mesh are incompatible: speculative "
                "decoding under a device mesh is not implemented")
        if args.share_prefix == "on":
            raise SystemExit(
                "--spec-k and --share-prefix on are incompatible: the "
                "draft pool rides the target's page tables but COW forks "
                "only cover the target pool (leave --share-prefix auto)")
        if args.family in _RECURRENT_FAMILIES:
            raise SystemExit(
                f"--spec-k and --family {args.family} are incompatible: "
                "recurrent state cannot roll back rejected draft tokens")
        if args.paged == "off":
            raise SystemExit(
                "--spec-k and --paged off are incompatible: speculative "
                "rollback is arithmetic on the paged per-slot lengths")
    if args.kv_dtype == "int8":
        if args.paged == "off":
            raise SystemExit(
                "--kv-dtype int8 and --paged off are incompatible: "
                "quantized KV lives in the paged block pool")
        if args.spec_k > 0:
            raise SystemExit(
                "--kv-dtype int8 and --spec-k are incompatible: the "
                "draft/verify path is not quantization-aware")
        if args.mesh is not None:
            raise SystemExit(
                "--kv-dtype int8 and --mesh are incompatible: the scale "
                "pools have no sharding specs yet")


def model_config(args) -> ModelConfig:
    """The served config: a --family demo model or the --arch config
    (reduced and float32 under --smoke)."""
    if args.family != "arch":
        cfg = FAMILY_CONFIGS[args.family]
    else:
        cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    return cfg


def init_params(model, key, mesh=None):
    """Random weights from ``key``, made by one jitted program.  Under a
    mesh they are created in their serving shardings, one shard per
    device, so a model larger than one chip never lands whole on the
    first device; the values do not depend on the mesh."""
    shardings = None
    if mesh is not None:
        from ..models.sharding import param_shardings
        shardings = param_shardings(mesh, jax.eval_shape(model.init, key))
    return jax.jit(model.init, out_shardings=shardings)(key)


def build_engine(args, cfg, mesh=None) -> ServeEngine:
    """The paged ``ServeEngine`` the flags describe, with random weights
    (seed 0; the draft model's seed 1)."""
    model = build_model(cfg)
    params = init_params(model, jax.random.PRNGKey(0), mesh)
    draft_model = draft_params = None
    if args.spec_k > 0:
        name = args.draft_config or "tiny"
        if name == "tiny":
            # shrunken copy of the target: half the layers and width,
            # same head_dim and (crucially) the same vocabulary
            dcfg = cfg.replace(
                arch_id=f"{cfg.arch_id}-draft",
                n_layers=max(1, cfg.n_layers // 2),
                d_model=max(2 * cfg.n_heads, cfg.d_model // 2),
                n_heads=max(1, cfg.n_heads // 2),
                n_kv_heads=max(1, min(cfg.n_kv_heads, cfg.n_heads // 2)),
                d_ff=max(4, cfg.d_ff // 2) if cfg.d_ff else cfg.d_ff)
        else:
            dcfg = get_config(name, smoke=args.smoke)
        if args.smoke:
            dcfg = dcfg.replace(param_dtype="float32",
                                compute_dtype="float32")
        draft_model = build_model(dcfg)
        draft_params = draft_model.init(jax.random.PRNGKey(1))
        print(f"speculative decoding: K={args.spec_k}, draft "
              f"{dcfg.arch_id} ({dcfg.n_layers}L d{dcfg.d_model})")
    tri = {"auto": None, "on": True, "off": False}
    return ServeEngine(model, params, batch_size=args.batch,
                       capacity=args.prompt_len + args.max_new + 8,
                       max_new_tokens=args.max_new,
                       paged=tri[args.paged],
                       block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       prefill_chunk=args.prefill_chunk,
                       share_prefix=tri[args.share_prefix],
                       num_state_slots=args.num_state_slots,
                       burst=args.burst,
                       temperature=args.temperature,
                       top_k=args.top_k, seed=args.seed,
                       mesh=mesh, retain_cap=args.retain_cap,
                       retain_ttl_s=args.retain_ttl_s,
                       draft_model=draft_model, draft_params=draft_params,
                       spec_k=args.spec_k, kv_dtype=args.kv_dtype)


def make_requests(vocab: int, n: int, max_len: int, *, min_len: int = 4,
                  shared: int = 0) -> List[np.ndarray]:
    """``n`` random prompts (seed 0) with lengths in [max(min_len,
    shared + 1), max_len), all starting with the same ``shared`` tokens."""
    rng = np.random.default_rng(0)
    head = rng.integers(0, vocab, shared).astype(np.int32)
    lengths = [int(rng.integers(max(min_len, shared + 1), max_len))
               for _ in range(n)]
    return [np.concatenate([head, rng.integers(0, vocab, k - shared)
                            .astype(np.int32)]) for k in lengths]


def serve_over_tcp(engine, requests, *, port: int, lanes=("interactive",),
                   pad_to: Optional[int] = None, max_wait_ms: float = 5.0):
    """Serve ``requests`` through the front door: a ``TensorQueryServer``
    on ``port`` (0 = ephemeral) (serversrc -> batcher -> engine filter
    -> unbatcher -> serversink) and a loopback ``TensorQueryClient``.
    Returns (results in request order, wall seconds from first submit
    to last result)."""
    from ..serving import TensorQueryClient, TensorQueryServer
    server = TensorQueryServer(engine, port=port, max_wait_ms=max_wait_ms,
                               pad_to=pad_to).start()
    try:
        t0 = time.perf_counter()
        client = TensorQueryClient("127.0.0.1", server.port)
        try:
            qids = [client.submit(r, lane=lanes[i % len(lanes)])
                    for i, r in enumerate(requests)]
            rs = [client.result(q, timeout=300) for q in qids]
        finally:
            client.close()
        return rs, time.perf_counter() - t0
    finally:
        server.stop()


def print_scheduler_stats(engine) -> None:
    print(f"scheduler: prefills={engine.n_prefills} "
          f"joins={engine.n_joins} evictions={engine.n_evictions} "
          f"preemptions={engine.n_preemptions} "
          f"restores={engine.n_restores} expired={engine.n_expired}"
          + (f" prefill_chunks={engine.n_prefill_chunks}" if engine.paged
             else ""))
    ls = engine.loop_stats()
    decoded = max(1, ls["n_device_steps"])
    print(f"decode loop: burst K={ls['burst']}, {ls['n_bursts']} bursts / "
          f"{ls['n_device_steps']} device steps, "
          f"{ls['n_host_syncs']} host syncs "
          f"({ls['n_host_syncs'] / decoded:.2f}/step), "
          f"{ls['n_state_uploads']} state uploads, "
          f"{ls['n_burst_early_exits']} early exits")
    _print_spec_stats(engine)


def _standing_server(engine, args, lanes) -> None:
    """Serve until SIGTERM/SIGINT, then drain gracefully: stop
    admitting, finish (or cancel) in-flight work so every client holds
    a terminal frame."""
    import signal
    from ..serving import TensorQueryServer
    server = TensorQueryServer(engine, port=args.listen,
                               max_wait_ms=args.max_wait_ms_net,
                               pad_to=args.prompt_len).start()
    print(f"tensor_query server listening on 127.0.0.1:{server.port} "
          f"(lanes: {', '.join(lanes)})")
    stop_evt = threading.Event()

    def _on_signal(signum, frame):
        del frame
        print(f"signal {signum}: draining "
              f"(timeout {args.drain_timeout_s:.0f}s)", flush=True)
        stop_evt.set()
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        while not stop_evt.wait(timeout=0.2):
            pass
        clean = server.drain(timeout=args.drain_timeout_s)
        print("drain complete" if clean
              else "drain timed out: remaining requests cancelled",
              flush=True)
    finally:
        server.stop()


def main(argv=None) -> int:
    """Returns the exit code: 1 if any request ended in a status other
    than ``ok``."""
    args = build_parser().parse_args(argv)
    validate_args(args)
    enable_compile_cache()

    cfg = model_config(args)
    mesh = None
    if args.mesh is not None:
        from .mesh import make_serving_mesh
        mesh = make_serving_mesh(model=args.mesh)
        print(f"serving over mesh {dict(mesh.shape)}"
              f" ({jax.device_count()} device(s) visible)")
    engine = build_engine(args, cfg, mesh)
    requests = make_requests(cfg.vocab_size, args.requests, args.prompt_len,
                             shared=args.shared_prompt)
    lanes = [l.strip() for l in args.lanes.split(",") if l.strip()]

    if args.listen is not None and not args.smoke:
        _standing_server(engine, args, lanes)
        return 0

    t0 = time.perf_counter()
    if args.listen is not None:
        rs, wall = serve_over_tcp(engine, requests, port=args.listen,
                                  lanes=lanes, pad_to=args.prompt_len,
                                  max_wait_ms=args.max_wait_ms_net)
        statuses = [r.status for r in rs]
        total_tokens = sum(len(r.tokens) for r in rs if r.tokens is not None)
        print(f"served {len(rs)} requests / {total_tokens} tokens over TCP "
              f"in {wall:.2f}s ({total_tokens / wall:.1f} tok/s)")
        for r in rs[:3]:
            print(f"  qid {r.qid}: status={r.status} "
                  f"ttft={r.ttft_s:.3f}s tokens={list(r.tokens[:8])}...")
    elif args.direct:
        results = engine.serve(requests)
        wall = time.perf_counter() - t0
        statuses = [r.status for r in results]
        total_tokens = sum(len(r.tokens) for r in results)
        print(f"served {len(results)} requests / {total_tokens} tokens "
              f"in {wall:.2f}s ({total_tokens / wall:.1f} tok/s)")
        for r in results[:3]:
            print(f"  req {r.request_id}: prompt[{len(r.prompt)}] -> "
                  f"{r.tokens[:8]}... latency={r.latency_s:.3f}s")
    else:
        from ..core import parse_pipeline
        pipe = parse_pipeline(
            "appsrc name=req ! tensor_batcher max_batch=%d max_wait_ms=%s ! "
            "queue max_size=8 ! tensor_filter framework=python model=llm "
            "max_batch=%d pass_meta=true ! tensor_unbatcher ! "
            "tensor_sink name=out keep=true"
            % (args.batch, args.max_wait_ms, args.batch),
            models={"llm": engine.as_pipeline_filter(use_meta=True)})
        pipe.start()
        # batcher stacks frames, so left-pad prompts to a common length;
        # the engine filter strips the padding by meta["query"]
        maxlen = max(len(r) for r in requests)
        for i, r in enumerate(requests):
            pipe["req"].push(np.pad(r, (maxlen - len(r), 0)),
                             meta={"request": i,
                                   "query": {"prompt_len": len(r)}})
        pipe["req"].end_of_stream()
        pipe["out"].eos_seen.wait(timeout=300)
        pipe.stop()
        results = pipe["out"].buffers
        wall = time.perf_counter() - t0
        statuses = [b.meta.get("status") for b in results]
        statuses += ["lost"] * (len(requests) - len(results))
        total_tokens = sum(np.asarray(b.data).size for b in results)
        print(f"served {len(results)} requests / {total_tokens} tokens "
              f"in {wall:.2f}s ({total_tokens / wall:.1f} tok/s)")
        for b in results[:3]:
            print(f"  req {b.meta.get('request')}: "
                  f"prompt_len={b.meta['query']['prompt_len']} -> "
                  f"{np.asarray(b.data)[:8]}...")

    print_scheduler_stats(engine)
    if engine.paged:
        a = engine.allocator
        s = engine.pool_stats()
        print(f"paged cache: {a.num_blocks} blocks x {a.block_size} tokens, "
              f"{s['n_free']} free / {s['n_shared']} shared / "
              f"{s['n_private']} private after drain")
        print(f"kv storage: {s['kv_dtype']}, {s['bytes_per_block']} "
              f"bytes/block, {s['pool_bytes'] / 1e6:.2f} MB pool")
        if engine.state_store is not None:
            print(f"state store: {s['num_state_slots']} slabs, "
                  f"{s['n_state_free']} free / {s['n_state_live']} live "
                  "after drain (recurrent layers)")
        if engine.share_prefix:
            print(f"prefix sharing: {engine.n_prefix_hits} hits, "
                  f"{engine.n_shared_tokens} prompt tokens served from "
                  f"resident blocks, {engine.n_cow_forks} COW forks")
    failed = [st for st in statuses if st != "ok"]
    if failed:
        print(f"FAILED: {len(failed)} of {len(statuses)} requests did not "
              f"end ok (statuses: {sorted(set(map(str, failed)))})")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
