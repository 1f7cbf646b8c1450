"""Spans inside the serving path.

The per-request server-timing record (the TIMING frame each DONE is
preceded by, folded into ``QueryResult.server_timing``) and the engine's
step spans on the profiler's host plane.  The toy model from
test_serve_continuous keeps the wire tests free of compilation; the
engine tests use the tiny paged transformer of conftest.
"""
import glob
import socket
import time

import jax
import numpy as np
import pytest

from conftest import TINY_SERVE
from repro.core.elements.query import (MSG_DONE, MSG_REQUEST, MSG_TIMING,
                                       STATUS_NAMES, TIMING_FIELDS,
                                       pack_frame, pack_tensor, read_frame,
                                       unpack_tensor)
from repro.serving import ServeEngine, TensorQueryClient, TensorQueryServer
from repro.serving.faults import Fault, FaultPlan

from test_serve_continuous import ToyModel, _expected

EOS = 10


def _slow_steps(eng, pause_s):
    """Make every engine tick take at least ``pause_s``, so that time
    spent in the engine is long against the front door's."""
    step = eng.step

    def slow():
        time.sleep(pause_s)
        return step()
    eng.step = slow


@pytest.fixture()
def toy():
    eng = ServeEngine(ToyModel(), params={}, batch_size=4, capacity=96,
                      max_new_tokens=60, eos_id=EOS)
    srv = TensorQueryServer(eng, max_wait_ms=5.0, pad_to=16).start()
    yield eng, srv
    srv.stop()


def _wait_until(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def test_every_done_is_preceded_by_its_timing_record(toy):
    eng, srv = toy
    prompts = {q: np.asarray([q + 20, q + 21], np.int32) for q in range(4)}
    raw = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
    for q, p in prompts.items():
        raw.sendall(pack_frame(MSG_REQUEST, q, pack_tensor(p)))
    last = {}                          # qid -> type of its previous frame
    records = {}
    while len(records) < len(prompts) or any(
            last.get(q) != MSG_DONE for q in prompts):
        msg, qid, _, status, _, payload = read_frame(raw)
        if msg == MSG_DONE:
            assert last.get(qid) == MSG_TIMING, (qid, last.get(qid))
            assert STATUS_NAMES[status] == "ok"
            assert list(unpack_tensor(payload)) == _expected(
                prompts[qid], 60, EOS)
        if msg == MSG_TIMING:
            rec = unpack_tensor(payload)
            assert rec.dtype == np.float32
            assert rec.shape == (len(TIMING_FIELDS),)
            records[qid] = rec
        last[qid] = msg
    raw.close()
    for rec in records.values():
        assert (rec >= 0).all()

    # the client folds the record into the result before DONE sets it
    cli = TensorQueryClient("127.0.0.1", srv.port)
    for q, p in prompts.items():
        r = cli.result(cli.submit(p), timeout=60)
        assert r.status == "ok"
        t = r.server_timing
        assert t is not None and set(t) == set(TIMING_FIELDS)
        assert all(v >= 0 for v in t.values())
        # every duration lies inside the client's send -> DONE
        assert sum(t.values()) <= r.latency_s
        # send -> first token covers arrival -> first token
        assert t["ingress"] + t["queue"] + t["prefill"] <= r.ttft_s
    cli.close()


def test_short_request_waits_for_its_micro_batch_slowest_member():
    """The unbatcher releases a micro-batch only once its slowest
    request finished: a short request batched with a long one is held
    about the long one's extra time, and the record shows it as
    ``hold``."""
    eng = ServeEngine(ToyModel(), params={}, batch_size=4, capacity=96,
                      max_new_tokens=60, eos_id=EOS)
    _slow_steps(eng, 0.005)
    # a wide window so that both requests land in one micro-batch
    srv = TensorQueryServer(eng, max_wait_ms=300.0, max_batch=2,
                            pad_to=16).start()
    try:
        cli = TensorQueryClient("127.0.0.1", srv.port)
        short = cli.submit(np.asarray([3, 5], np.int32))   # 9, 10 (eos)
        long_ = cli.submit(np.asarray([20, 21], np.int32))
        rs = cli.result(short, timeout=60)
        rl = cli.result(long_, timeout=60)
        cli.close()
    finally:
        srv.stop()
    assert list(rs.tokens) == [9, 10]
    assert len(rl.tokens) > 20
    ts, tl = rs.server_timing, rl.server_timing

    def engine_time(t):
        return t["queue"] + t["prefill"] + t["decode"]
    extra = engine_time(tl) - engine_time(ts)
    assert extra > 0.05
    # held for the long request's extra time (both were submitted in
    # the same filter call, microseconds apart) ...
    assert ts["hold"] >= 0.8 * extra
    # ... which the long request itself does not pay
    assert tl["hold"] < 0.5 * ts["hold"]
    # the client saw the short request's DONE only after the hold
    assert rs.latency_s >= ts["hold"]


def test_error_and_cancelled_requests_end_cleanly():
    # a poison row fails at submit, before any stamp: ERROR, no record
    plan = FaultPlan([Fault(point="submit", nth=2, action="raise")])
    eng = ServeEngine(ToyModel(), params={}, batch_size=4, capacity=260,
                      max_new_tokens=200, fault_plan=plan)
    _slow_steps(eng, 0.005)
    srv = TensorQueryServer(eng, max_wait_ms=5.0, pad_to=16).start()
    try:
        cli = TensorQueryClient("127.0.0.1", srv.port)
        # oversized: rejected by the server source, never in the engine
        big = cli.result(cli.submit(np.ones(17, np.int32)), timeout=30)
        assert big.status == "error" and big.server_timing is None
        first = cli.result(cli.submit(np.asarray([1, 2], np.int32)),
                           timeout=60)
        assert first.status == "ok" and first.server_timing is not None
        poison = cli.result(cli.submit(np.asarray([2, 3], np.int32)),
                            timeout=30)
        assert poison.status == "error" and poison.server_timing is None
        # cancelled mid-stream: it was admitted and streamed, so its
        # record is whole and precedes the DONE(cancelled)
        qid = cli.submit(np.asarray([1, 2, 3], np.int32))
        _wait_until(lambda: cli._requests[qid].stream,
                    what="first streamed token")
        cli.cancel(qid)
        r = cli.result(qid, timeout=30)
        assert r.status == "cancelled" and 0 < len(r.tokens) < 200
        t = r.server_timing
        assert t is not None and all(v >= 0 for v in t.values())
        assert sum(t.values()) <= r.latency_s
        # cancelled while queued: never admitted, no record, still DONE
        cli2 = TensorQueryClient("127.0.0.1", srv.port)
        _wait_until(lambda: len(srv.src.connections) == 2,
                    what="second connection")
        blockers = [cli2.submit(np.asarray([i + 1, 4], np.int32))
                    for i in range(4)]
        _wait_until(lambda: eng.n_active == 4, what="slots to fill")
        q = cli2.submit(np.asarray([7, 7], np.int32))
        _wait_until(lambda: eng.scheduler.pending, what="queued request")
        cli2.cancel(q)
        rq = cli2.result(q, timeout=30)
        assert rq.status == "cancelled" and rq.server_timing is None
        for b in blockers:
            cli2.cancel(b)
        for b in blockers:
            rb = cli2.result(b, timeout=30)
            # a cancel can land after the request finished
            assert rb.status in ("ok", "cancelled")
            assert rb.server_timing is not None
        _wait_until(lambda: not srv._routes, what="routes to drain")
        cli.close()
        cli2.close()
    finally:
        srv.stop()


@pytest.fixture(scope="module")
def tiny_lm():
    from repro.models import build_model
    model = build_model(TINY_SERVE)
    return model, model.init(jax.random.PRNGKey(0))


def _paged(tiny_lm, **kw):
    model, params = tiny_lm
    kw.setdefault("batch_size", 2)
    return ServeEngine(model, params, capacity=32, max_new_tokens=6,
                       paged=True, block_size=4, prefill_chunk=8, burst=4,
                       **kw)


def test_engine_stamps_in_order_and_kept_across_preemption(tiny_lm):
    eng = _paged(tiny_lm)
    prompt = np.arange(1, 12, dtype=np.int32)
    rid = eng.submit(prompt, lane="batch")
    while not eng._slots[0] or not eng._slots[0].tokens:
        eng.step()
    admitted, first = eng._slots[0].t_admit, eng._slots[0].t_first
    assert eng.preempt(rid)
    (res,) = eng.wait([rid], timeout_s=60)
    assert eng.n_restores == 1 and res.status == "ok"
    # the restore keeps the first admission and the first token
    assert (res.t_admit, res.t_first) == (admitted, first)
    assert res.t_submit <= res.t_admit <= res.t_first <= res.t_finish
    assert res.ttft_s == pytest.approx(res.t_first - res.t_submit)


def test_profiler_trace_holds_the_engine_spans(tiny_lm, tmp_path):
    eng = _paged(tiny_lm)
    eng.serve([np.arange(1, 12, dtype=np.int32)])     # compile outside
    eng.submit(np.arange(3, 9, dtype=np.int32))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.step()                  # admit, then a mixed step
        eng.step()                  # a burst
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names, steps = set(), set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name == "engine_step":
                    steps.add(dict(list(e.stats))["step_num"])
    assert {"engine_step", "engine.admit", "engine.evict", "engine.prepare",
            "engine.dispatch", "engine.drain", "engine.emit"} <= names
    assert len(steps) == 2
