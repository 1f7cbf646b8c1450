"""Smoke run of the serving path on a TPU chip.  Not a benchmark.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips, tensor-parallel only

One chip: smollm-360m at its published widths (32 layers, d_model 960,
15/5 heads, vocab 49152, bf16, random weights from seed 0) is served
through the tensor-query front door exactly as ``python -m
repro.launch.serve --listen 0`` serves it -- TensorQueryServer
(serversrc -> batcher -> engine filter -> unbatcher -> serversink) and a
loopback TensorQueryClient -- and then a few camera frames go through
``tensor_transform backend=fused``, the compiled Pallas transform
kernel.  Every request must end ok with the right number of in-vocab
tokens and no engine step failure, each request's first token must
match a plain full-sequence forward of the same weights on the chip,
and every frame must match the kernel's jnp reference.

Four chips: glm4-9b at full width (40 layers, ~18.8 GB of bf16 weights)
is served in bf16 over a (1, 4) tensor-parallel mesh, with its weights
created in their shardings, and its first tokens are checked against a
plain forward on the same mesh as above.  Then glm4-9b cut to 8 of its
40 layers (5.35 GiB of bf16 weights, float32 compute at full matmul
precision) is served at mesh 4 and at mesh 1 and must decode identical
tokens.

Exits non-zero, printing no result, when JAX finds no TPU.  The last
line of a run that passed is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The times and memory it prints are smoke numbers: compile time, the
wall time of a handful of requests, peak HBM.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch import serve as launcher  # noqa: E402

SMOLLM = "smollm-360m"
N_REQUESTS, BATCH, MAX_NEW = 8, 4, 16
PROMPT_MIN, PROMPT_MAX = 32, 128
# A served first token may differ from the reference's argmax only where
# the reference's top-1/top-2 logit gap is below this many bf16 ulps of
# the top-1 logit.  Both sides compute in bf16 (8-bit significand, so
# one ulp is 2**-7 of the value's binade), but by different routes --
# chunked paged prefill with an f32 KV pool against one naive
# full-sequence attention, and under a mesh with sums split across
# chips -- so over tens of layers the logits differ by a few ulps, and
# a near-tie may resolve either way.
TIE_ULPS = 4
FRAME = (480, 640, 3)
N_FRAMES = 4


class CompileClock:
    """Sums JAX's own compile-time events over the process: backend
    compile (or, on a persistent-cache hit, cache retrieval) seconds,
    tracing plus lowering seconds, and persistent-cache hits."""

    def __init__(self):
        import collections
        import jax
        self.backend_s = self.trace_lower_s = 0.0
        self.cache_hits = 0
        self.by_fun = collections.Counter()

        def on_duration(event, secs, fun_name="?", **kw):
            del kw
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend_s += secs
                self.by_fun[fun_name] += secs
            elif event in ("/jax/core/compile/jaxpr_trace_duration",
                           "/jax/core/compile/jaxpr_to_mlir_module_duration"):
                self.trace_lower_s += secs

        def on_event(event, **kw):
            del kw
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compile_s={self.backend_s:.2f} "
                f"trace_lower_s={self.trace_lower_s:.2f} "
                f"persistent_cache_hits={self.cache_hits}")

    def top(self) -> str:
        """The four programs that took longest to compile."""
        return ", ".join(f"{name} {secs:.2f}s"
                         for name, secs in self.by_fun.most_common(4))


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(ok, msg) -> None:
    """A check that stays under ``python -O``, unlike ``assert``."""
    if not ok:
        raise SmokeFailure(msg)


def _serve_args(arch, *, requests, batch, max_new, prompt_len):
    return launcher.build_parser().parse_args(
        ["--arch", arch, "--listen", "0", "--requests", str(requests),
         "--batch", str(batch), "--max-new", str(max_new),
         "--prompt-len", str(prompt_len)])


def serve(cfg, args, requests, mesh=None):
    """Serve ``requests`` through the front door on a fresh engine;
    checks every request ended ok with ``max_new`` in-vocab tokens and
    that the engine never failed a step.  Returns (engine, results,
    wall seconds)."""
    engine = launcher.build_engine(args, cfg, mesh)
    rs, wall = launcher.serve_over_tcp(engine, requests, port=args.listen,
                                       pad_to=args.prompt_len,
                                       max_wait_ms=args.max_wait_ms_net)
    require(len(rs) == len(requests),
            f"{len(rs)} results for {len(requests)} requests")
    for r in rs:
        require(r.status == "ok", f"qid {r.qid}: status={r.status} {r.error}")
        n = 0 if r.tokens is None else len(r.tokens)
        require(n == args.max_new,
                f"qid {r.qid}: {n} tokens, expected {args.max_new}")
        require(r.tokens.min() >= 0 and r.tokens.max() < cfg.vocab_size,
                f"qid {r.qid}: token outside vocab [0, {cfg.vocab_size})")
    require(engine.n_step_failures == 0 and engine.n_restarts == 0,
            f"engine step failures={engine.n_step_failures} "
            f"restarts={engine.n_restarts}")
    return engine, rs, wall


def check_first_tokens(engine, requests, results):
    """Each request's first generated token against the argmax of a
    plain full-sequence ``model.apply`` of the same weights, on the
    engine's mesh if it has one, at the last prompt position (prompts
    right-padded into one batch: the causal mask keeps padding out of
    every earlier position)."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = engine.model
    width = max(len(r) for r in requests)
    toks = np.zeros((len(requests), width), np.int32)
    for i, r in enumerate(requests):
        toks[i, :len(r)] = r
    last = np.asarray([len(r) - 1 for r in requests], np.int32)

    @jax.jit
    def last_logits(params, toks, last):
        logits, _ = model.apply(params, toks)
        return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]

    with (engine.mesh if engine.mesh is not None
          else contextlib.nullcontext()):
        ref = np.asarray(last_logits(engine.params, toks, last), np.float32)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top2[:, 1]),
                                              1e-30))) - 7)
    n_tie = 0
    for i, r in enumerate(results):
        want = int(ref[i].argmax())
        if int(r.tokens[0]) != want:
            require(gap[i] < TIE_ULPS * ulp[i],
                    f"request {i}: first token {int(r.tokens[0])} != "
                    f"reference argmax {want} (top-1/top-2 gap "
                    f"{gap[i]:.4g}, {gap[i] / ulp[i]:.1f} bf16 ulps)")
            n_tie += 1
    print(f"reference check: {len(results) - n_tie}/{len(results)} first "
          f"tokens equal the full-sequence argmax, {n_tie} near-ties "
          f"forgiven (gap < {TIE_ULPS} bf16 ulps); min gap "
          f"{float((gap / ulp).min()):.1f} ulps")


def serve_phase(cfg, clock):
    """smollm-360m through the front door, checked against the plain
    forward."""
    args = _serve_args(cfg.arch_id, requests=N_REQUESTS, batch=BATCH,
                       max_new=MAX_NEW, prompt_len=PROMPT_MAX)
    requests = launcher.make_requests(cfg.vocab_size, N_REQUESTS,
                                      PROMPT_MAX + 1, min_len=PROMPT_MIN)
    t0 = time.perf_counter()
    engine, rs, wall = serve(cfg, args, requests)
    total = sum(len(r.tokens) for r in rs)
    print(f"serve: {cfg.arch_id} {cfg.n_layers}L d{cfg.d_model} "
          f"{cfg.param_dtype}: {len(rs)} requests ok "
          f"({engine.n_requests} submitted to the engine), {total} tokens, "
          f"prompts {min(map(len, requests))}-{max(map(len, requests))}, "
          f"wall {wall:.2f}s incl. compile (build+serve "
          f"{time.perf_counter() - t0:.2f}s); {clock.line()}")
    launcher.print_scheduler_stats(engine)
    check_first_tokens(engine, requests, rs)


def transform_phase():
    """Camera frames through ``tensor_transform backend=fused`` against
    the kernel's jnp reference."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import parse_pipeline
    from repro.kernels import default_interpret
    from repro.kernels.transform.ref import fused_transform_ref
    require(not default_interpret(), "the transform kernel would interpret")
    pipe = parse_pipeline(
        "appsrc name=src ! tensor_transform backend=fused "
        "option=typecast:float32,divide:255.0,subtract:0.5 ! "
        "tensor_sink name=out keep=true")
    frames = np.random.default_rng(0).integers(
        0, 256, (N_FRAMES,) + FRAME).astype(np.uint8)
    t0 = time.perf_counter()
    pipe.start()
    for f in frames:
        pipe["src"].push(f)
    pipe["src"].end_of_stream()
    require(pipe["out"].eos_seen.wait(timeout=600), "frame pipeline stalled")
    pipe.stop()
    wall = time.perf_counter() - t0
    outs = [np.asarray(b.data) for b in pipe["out"].buffers]
    require(len(outs) == N_FRAMES, f"{len(outs)} of {N_FRAMES} frames out")
    for f, y in zip(frames, outs):
        want = np.asarray(fused_transform_ref(
            jnp.asarray(f), 1 / 255.0, -0.5, -np.inf, np.inf, jnp.float32))
        require(y.shape == FRAME and y.dtype == np.float32,
                f"frame out as {y.shape} {y.dtype}")
        np.testing.assert_allclose(y, want, rtol=0, atol=1e-6)
    print(f"transform: {N_FRAMES} frames {FRAME} uint8 -> float32 through "
          f"the compiled kernel match the reference; wall {wall:.2f}s "
          "incl. compile")


def tp_phase(full_cfg, cut_layers, clock, n_chips):
    """The full config served over an ``n_chips``-way tensor-parallel
    mesh and checked against the plain forward; then the config cut to
    ``cut_layers`` layers served at mesh ``n_chips`` and at mesh 1,
    which must decode identical tokens."""
    import jax
    from repro.launch.mesh import make_serving_mesh
    args = _serve_args(full_cfg.arch_id, requests=4, batch=4, max_new=8,
                       prompt_len=64)
    requests = launcher.make_requests(full_cfg.vocab_size, 4, 65,
                                      min_len=16)
    mesh = make_serving_mesh(model=n_chips)
    engine, rs, wall = serve(full_cfg, args, requests, mesh)
    print(f"tp serve: {full_cfg.arch_id} {full_cfg.n_layers}L "
          f"d{full_cfg.d_model} {full_cfg.param_dtype} over mesh "
          f"{dict(mesh.shape)}: {len(rs)} requests ok, wall {wall:.2f}s "
          f"incl. compile; {clock.line()}; {_hbm_line()}")
    check_first_tokens(engine, requests, rs)
    del engine, rs
    gc.collect()
    # token identity is the sharded-serving contract, held with float32
    # compute over the bf16 weights at full float32 matmul precision.
    # At the TPU's default precision a float32 matmul rounds its inputs
    # to bf16, and a shard's partial sums then differ from one chip's
    # by enough to flip a greedy near-tie of these random weights (on a
    # v5e host, with only the precision changed, 1 of 4 requests
    # diverged from its second token).
    jax.config.update("jax_default_matmul_precision", "highest")
    cut = full_cfg.replace(n_layers=cut_layers, compute_dtype="float32")
    tokens = {}
    for n in (n_chips, 1):
        engine, rs, _ = serve(cut, args, requests,
                              make_serving_mesh(model=n) if n > 1 else None)
        tokens[n] = [r.tokens.tolist() for r in rs]
        del engine, rs
        gc.collect()
    require(tokens[n_chips] == tokens[1],
            f"mesh {n_chips} and mesh 1 decoded different tokens: {tokens}")
    print(f"tp identity: {full_cfg.arch_id} cut to {cut_layers} layers, "
          f"float32 compute at highest matmul precision: mesh {n_chips} "
          f"and mesh 1 decode identical tokens "
          f"({sum(map(len, tokens[1]))} tokens); {clock.line()}")


def _hbm_line() -> str:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return "peak_hbm_gib=" + ",".join(
        f"{s['peak_bytes_in_use'] / 2**30:.2f}" if "peak_bytes_in_use" in s
        else "not-reported" for s in stats)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel glm4-9b phase")
    args = ap.parse_args()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"chip smoke run (not a benchmark): {dev.platform} "
          f"{dev.device_kind} x{len(devices)}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        serve_phase(get_config(SMOLLM), clock)
        transform_phase()
    else:
        tp_phase(get_config("glm4-9b"), 8, clock, args.chips)
    print(f"smoke total wall {time.perf_counter() - t0:.2f}s; "
          f"{clock.line()}; {_hbm_line()}")
    print(f"largest compiles: {clock.top()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
