"""One run of one cell: build, warm up, fill, measure, check.

Everything between process start and the window's opening is set-up:
making the weights, building the engine and the front door, compiling
(or reading from the persistent cache) the cell's two megasteps by
serving a few warm-up requests, and loading the server (an open loop's
lead-in arrivals, or a closed loop's users until every slot is busy).
Then the window runs for ``seconds``; with ``trace`` the profiler
records a slice in its middle.  After the window the open loop waits
for every request due in it (up to the mix's grace), the closed loop
stops; the peak memory is read, the server and engine are shut down,
and the sample of finished requests is compared with the plain
reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional


from . import check, devtrace, stats, traffic
from .clock import CompileClock
from .dims import Dims, dims as config_dims
from .load import Cell, metric_reader, reference_module

# program config fields the configuration's file must agree with
_SIZES = {"n_layers": "layers", "d_model": "d_model", "n_heads": "heads",
          "n_kv_heads": "kv_heads", "resolved_head_dim": "head_dim",
          "d_ff": "d_ff", "vocab_size": "vocab"}
TRACE_S = 6.0        # length of the traced slice, centred in the window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def counters(engine) -> Dict[str, int]:
    ls = engine.loop_stats()
    out = {k: int(ls[k]) for k in ("n_device_steps", "n_host_syncs",
                                   "n_bursts", "n_state_uploads")}
    for k in ("n_prefill_chunks", "n_prefills", "n_joins", "n_evictions",
              "n_preemptions", "n_step_failures", "n_restarts"):
        out[k] = int(getattr(engine, k))
    return out


def _delta(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    return {k: b[k] - a[k] for k in a}


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    t0: float
    t1: float
    t_close: float
    reqs: List[traffic.Req]
    c0: Dict[str, int]
    c1: Dict[str, int]
    p0: Dict[int, int]
    p1: Dict[int, int]
    trace: Optional[devtrace.Reduction] = None
    trace_window_s: float = 0.0
    ct0: Optional[Dict[str, int]] = None
    ct1: Optional[Dict[str, int]] = None

    @property
    def open_loop(self) -> bool:
        return self.cell.traffic["loop"] == "open"

    def scored(self) -> List[traffic.Req]:
        """The requests a tail is taken over: those due in the window
        (open loop), or those completed inside it (closed loop)."""
        if self.open_loop:
            return [r for r in self.reqs if r.in_window]
        return [r for r in self.reqs if r.ok
                and self.t0 <= r.res.t_done <= self.t1]

    def window_tokens(self) -> int:
        return stats.tokens_between(self.p0, self.p1)


def _check_sizes(pcfg, dims: Dims) -> None:
    for field, key in _SIZES.items():
        want, got = getattr(dims, key), getattr(pcfg, field)
        if want != got:
            raise ValueError(f"program config {pcfg.arch_id} has "
                             f"{field}={got}, the configuration's file "
                             f"{want}")


def _cache_dir(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _peak_bytes(devices) -> Optional[int]:
    peak = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]
    return max(peak) if any(peak) else None


def _shutdown(server, client, engine, loop) -> None:
    """Stop the load, cancel whatever is still in flight, and wait
    until the engine holds no work, so nothing steps the device after
    this returns."""
    if isinstance(loop, traffic.ClosedLoop):
        loop.stop()
    server.src.stop_accepting()
    deadline = time.monotonic() + 30.0
    quiet = 0
    while time.monotonic() < deadline and quiet < 3:
        rids = engine.inflight_rids()
        for rid in rids:
            engine.cancel(rid, "timeout")
        quiet = 0 if rids or engine.has_work else quiet + 1
        time.sleep(0.05)
    client.close()
    server.stop()


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, devices, control: bool = False) -> Dict[str, Any]:
    """One run; returns the result line's object."""
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model
    from repro.models.sharding import param_shardings
    from repro.serving import (ServeEngine, TensorQueryClient,
                               TensorQueryServer)
    from . import weights

    cfgf, mix = cell.config, cell.traffic
    clock = CompileClock()
    cache = _cache_dir(cell.root)
    dims = config_dims(cfgf)
    dev0 = devices[0]
    eng, srv = cfgf["engine"], cfgf["server"]
    if mix["prompt"]["max"] + mix["max_new"] > eng["capacity"]:
        raise ValueError("the mix's longest request exceeds the capacity")

    t = time.monotonic()
    pcfg = get_config(cfgf["arch"]).replace(**cfgf.get("program", {}))
    _check_sizes(pcfg, dims)
    if pcfg.tie_embeddings != bool(cfgf["config"].get("tie_word_embeddings",
                                                      False)):
        raise ValueError("the program's tie_embeddings differs from the "
                         "configuration's tie_word_embeddings")
    model = build_model(pcfg)
    mesh = make_serving_mesh(model=cfgf["mesh_model"]) \
        if cfgf["mesh_model"] > 1 else None
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, seed, param_shardings(mesh, shapes)
                          if mesh is not None else None)
    jax.block_until_ready(params)
    t_init = time.monotonic() - t

    engine = ServeEngine(
        model, params, batch_size=eng["batch_size"],
        capacity=eng["capacity"], max_new_tokens=mix["max_new"],
        paged=True, block_size=eng["block_size"],
        prefill_chunk=eng["prefill_chunk"], burst=eng["burst"],
        num_blocks=eng.get("num_blocks"), kv_dtype=eng["kv_dtype"],
        temperature=0.0, mesh=mesh)
    plan = traffic.Plan(mix, seed, seconds, dims.vocab)
    server = TensorQueryServer(engine, port=0,
                               max_wait_ms=srv["max_wait_ms"],
                               pad_to=plan.max_prompt,
                               workers=srv["workers"]).start()
    client = TensorQueryClient("127.0.0.1", server.port)
    lane = mix.get("lane", "interactive")

    # warm-up: a few requests run the mixed megastep (their prompts) and
    # the burst (their next tokens), which compiles both or reads them
    # from the cache; each is cancelled once a burst has streamed tokens
    t = time.monotonic()
    warm = [traffic.submit(client, p, lane)
            for p in plan.warmup(int(mix.get("warmup_requests", 4)))]
    give_up = time.monotonic() + 900.0
    for res in warm:
        while (len(res.stream) < 2 and not res.done.is_set()
               and time.monotonic() < give_up):
            time.sleep(0.01)
        if not res.done.is_set():
            client.cancel(res.qid)
        if not res.done.wait(60.0) or res.status not in ("ok", "cancelled"):
            raise RuntimeError(f"warm-up request failed: {res.status} "
                               f"{res.error}")
    t_warm = time.monotonic() - t

    # fill: load the server before the window opens
    t = time.monotonic()
    if mix["loop"] == "open":
        origin = time.monotonic() + 0.05 + float(mix.get("lead_in_s", 0.0))
        loop = traffic.OpenLoop(client, plan, origin, lane).start()
        time.sleep(max(0.0, origin - time.monotonic()))
    else:
        loop = traffic.ClosedLoop(client, plan, int(mix["concurrency"]),
                                  lane).start()
        # full: every slot busy, or as many as the block pool admits
        # (the count has stopped growing for a second)
        fill_by, best, since = time.monotonic() + 60.0, 0, time.monotonic()
        while time.monotonic() < fill_by:
            n = engine.n_active
            if n >= eng["batch_size"]:
                break
            if n > best:
                best, since = n, time.monotonic()
            elif best and time.monotonic() - since > 1.0:
                break
            time.sleep(0.01)
    t_fill = time.monotonic() - t

    # the window
    t0 = time.monotonic()
    setup_s = t0 - t_process
    k0 = clock.compiles
    c0, p0 = counters(engine), {id(r): r.progress() for r in loop.reqs}
    red, trace_s = None, 0.0
    ct0 = ct1 = None
    if trace:
        trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lead = max(0.0, (seconds - TRACE_S) / 2)
        time.sleep(lead)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ts0 = time.monotonic()
        ct0 = counters(engine)
        time.sleep(min(TRACE_S, seconds))
        ct1 = counters(engine)
        trace_s = time.monotonic() - ts0
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t1 = time.monotonic()
    c1, p1 = counters(engine), {id(r): r.progress() for r in loop.reqs}
    k_window = clock.compiles - k0

    # after the window
    if isinstance(loop, traffic.OpenLoop):
        loop.join(seconds + 60.0)
        t_close = t1 + float(mix.get("grace_s", 60.0))
        for r in loop.reqs:
            if r.in_window and r.res is not None:
                r.res.done.wait(max(0.0, t_close - time.monotonic()))
        t_close = min(t_close, time.monotonic())
    else:
        loop.stop()
        # the check needs finished requests: where the window finished
        # fewer than the sample, the ones in flight may end in the grace
        want = int(mix.get("sample", 8))
        t_close = t1 + float(mix.get("grace_s", 60.0))
        while (time.monotonic() < t_close and sum(
                1 for r in loop.reqs if r.ok and r.res.t_done >= t0) < want):
            time.sleep(0.05)
        t_close = min(t_close, time.monotonic())
    # what had not ended by the close is late, not wrong; the shutdown
    # below cancels it
    unfinished = {id(r) for r in loop.reqs
                  if r.res is not None and not r.res.done.is_set()}
    mem_peak = _peak_bytes(devices[:cell.chips])
    _shutdown(server, client, engine, loop)
    reqs = list(loop.reqs)
    del engine, server, client, loop
    gc.collect()

    if trace:
        red = devtrace.reduce(jax.profiler.ProfileData.from_file(
            devtrace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    run_ = Run(cell=cell, seed=seed, seconds=seconds, setup_s=setup_s,
               t0=t0, t1=t1, t_close=t_close, reqs=reqs, c0=c0, c1=c1,
               p0=p0, p1=p1, trace=red, trace_window_s=trace_s, ct0=ct0,
               ct1=ct1)

    # set-up and window attribution, before the result
    log(f"setup: total {setup_s:.3f}s; weights+model {t_init:.3f}s, "
        f"warm-up {t_warm:.3f}s, fill {t_fill:.3f}s; {clock.line()}; "
        f"compile cache {cache}")
    log(f"window: compiles inside the window {k_window}; engine counter "
        f"deltas {json.dumps(_delta(c0, c1))}")
    scored = run_.scored()
    late = [r.sent - r.due for r in scored]
    if late:
        log("generator lateness ms: p50 "
            f"{1e3 * stats.percentile(late, 50):.3f} p95 "
            f"{1e3 * stats.percentile(late, 95):.3f} over {len(late)} "
            "requests")

    if run_.open_loop and scored:
        # a backlog that grows through the window shows as rising thirds
        thirds = [[], [], []]
        for r, v in zip(scored, stats.ttft_values(scored, t_close)):
            thirds[min(2, int(3 * (r.due - t0) / seconds))].append(v)
        log("ttft p50 ms by third of the window: " + " / ".join(
            f"{1e3 * stats.percentile(x, 50):.1f}" if x else "-"
            for x in thirds)
            + f"; served tokens/s {run_.window_tokens() / seconds:.1f}")

    # correctness: every scored request that ended ended ok with
    # max_new in-vocab tokens, and the sample agrees with the reference
    errors = [r for r in scored if r.error is not None or (
        id(r) not in unfinished and r.res.status != "ok")]
    bad_len = [r for r in scored if r.ok and (
        len(r.res.tokens) != mix["max_new"]
        or int(r.res.tokens.min()) < 0
        or int(r.res.tokens.max()) >= dims.vocab)]
    late_n = sum(1 for r in scored if id(r) in unfinished)
    if errors:
        log("failed requests: " + "; ".join(sorted({
            f"{r.res.status if r.res else 'unsent'}: "
            f"{(r.error or r.res.error or '')[:120]}" for r in errors})))
    limits = _limits(cell)
    t = time.monotonic()
    finished = scored if run_.open_loop else [
        r for r in reqs if r.ok and t0 <= r.res.t_done <= t_close]
    picks = check.sample(finished, int(mix.get("sample", 8)), seed)
    ref = reference_module(cfgf["reference"], cell.root)
    got = check.compare(ref, params, cfgf, picks,
                        plan.max_prompt + mix["max_new"], control=control)
    t_ref = time.monotonic() - t
    limits = {"served_gap": limits["served_gap"], "errored": 0,
              "wrong_length": 0, "unsampled": 0}
    numbers = {"served_gap": got["served_gap"], "errored": len(errors),
               "wrong_length": len(bad_len),
               "unsampled": int(mix.get("sample", 8)) - len(picks)}
    correct, checks = check.verdict(numbers, limits)
    log(f"reference: {got['n_tokens']} served tokens of {len(picks)} "
        f"requests compared in {t_ref:.3f}s")

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        v = metric_reader(m["name"], cell.root)(run_)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(scored),
           "failed": len(errors) + late_n, "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = red.busy_s if red.chips else 0.0
        device["window_s"] = trace_s
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops()],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    if control:
        # the float8 reference in the program's place, judged alike
        c_ok, c_checks = check.verdict(
            dict(numbers, served_gap=got["control_gap"]), limits)
        out["control"] = {"correct": c_ok, "checks": c_checks}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out["checks"] = checks
    return out


def _limits(cell: Cell) -> Dict[str, float]:
    path = os.path.join(cell.root, "bench", "limits", cell.name + ".json")
    with open(path) as f:
        return json.load(f)
