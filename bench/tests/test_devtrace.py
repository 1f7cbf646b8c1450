"""The trace reduction on a small synthetic trace."""
import jax
import pytest

from harness import devtrace


def _plane(pid, name, lines, metas):
    out = [f"planes {{ id: {pid} name: \"{name}\""]
    for lid, (lname, evs) in enumerate(lines):
        out.append(f"  lines {{ id: {lid} name: \"{lname}\" timestamp_ns: 0")
        for mid, start_us, dur_us in evs:
            out.append(f"    events {{ metadata_id: {mid} "
                       f"offset_ps: {int(start_us * 1e6)} "
                       f"duration_ps: {int(dur_us * 1e6)} }}")
        out.append("  }")
    for mid, mname in metas.items():
        out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: \"{mname}\" }} }}")
    out.append("}")
    return "\n".join(out)


OPS = {1: "%fusion.12 = bf16[64,960]{1,0} fusion(%p.1)",
       2: "%all-reduce.3 = f32[64,960]{1,0} all-reduce(%x)",
       3: "%while.7 = (s32[], bf16[8]) while(%t)",
       4: "%all-gather-start = (f32[8], f32[32]) all-gather-start(%y)",
       5: "jit_mixed_step(42)", 6: "jit_burst(43)"}
HOST = {1: "ExecuteOnLocalDevices", 2: "PjitFunction(mixed_step)",
        3: "ThreadMain"}


def _trace():
    # chip 0: ops at [0,100) [100,150) [120,160) [300,400) [900,1000) us
    chip0 = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 0, 100), (2, 100, 50), (3, 120, 40),
                     (1, 300, 100), (4, 900, 100)]),
        ("XLA Modules", [(5, 0, 160), (5, 300, 100), (6, 900, 100)]),
    ], OPS)
    # chip 1: busy [0, 500) us, one all-reduce of 100 us
    chip1 = _plane(2, "/device:TPU:1", [
        ("XLA Ops", [(1, 0, 400), (2, 400, 100)]),
        ("XLA Modules", [(5, 0, 500)]),
    ], OPS)
    host = _plane(3, "/host:CPU", [
        ("python", [(3, 0, 5000)]),
        ("worker", [(2, 170, 120), (1, 410, 480)]),
    ], HOST)
    return jax.profiler.ProfileData.from_text_proto(
        "\n".join([chip0, chip1, host]))


def test_busy_union_programs_and_collectives():
    red = devtrace.reduce(_trace())
    assert [c.name for c in red.chips] == ["/device:TPU:0", "/device:TPU:1"]
    c0, c1 = red.chips
    # union: [0,160) + [300,400) + [900,1000) = 360 us; overlap counted once
    assert c0.busy_ns == pytest.approx(360e3)
    assert c1.busy_ns == pytest.approx(500e3)
    assert red.busy_s == pytest.approx((360e-6 + 500e-6) / 2)
    assert c0.collective_ns == pytest.approx(150e3)     # all-reduce + gather
    assert c1.collective_ns == pytest.approx(100e3)
    assert c0.op_ns["fusion.12 bf16[64,960]"] == pytest.approx(200e3)
    assert not any(k.startswith("while") for k in c0.op_ns)  # a container
    secs, calls = red.program("mixed_step")
    assert calls == 2
    assert secs == pytest.approx((260e-6 + 500e-6) / 2)
    assert red.program("burst") == (pytest.approx(100e-6 / 2), 1)
    assert red.program("no_such_program") == (0.0, 0)
    top = dict(red.top_ops())
    assert top["fusion.12 bf16[64,960]"] == pytest.approx(300e-6)
    assert devtrace.op_label(OPS[4]) == "all-gather-start f32[8]"


def test_idle_gaps_named_by_host_activity():
    red = devtrace.reduce(_trace())
    gaps = red.idle_gaps
    # chip 0 gaps: [160,300) 140 us, [400,900) 500 us; longest first
    assert [round(s * 1e6) for _, s in gaps] == [500, 140]
    assert gaps[0][0] == "ExecuteOnLocalDevices"      # covers most of it
    assert gaps[1][0] == "PjitFunction(mixed_step)"


def test_no_device_planes_reduces_to_nothing():
    host = _plane(3, "/host:CPU", [("python", [(3, 0, 50)])], HOST)
    red = devtrace.reduce(jax.profiler.ProfileData.from_text_proto(host))
    assert red.chips == [] and red.idle_gaps == []


def test_metric_readers_fail_loudly_on_a_missing_program():
    """A reader whose program ran (by the engine's counters) but is not
    in the trace raises instead of reporting nothing."""
    import os
    from harness.load import metric_reader
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    red = devtrace.reduce(_trace())
    red.chips[0].programs.pop("jit_mixed_step")
    red.chips[1].programs.pop("jit_mixed_step")

    class Run:
        trace = red
        ct0 = {"n_prefill_chunks": 0, "n_device_steps": 0}
        ct1 = {"n_prefill_chunks": 5, "n_device_steps": 9}
    with pytest.raises(KeyError, match="mixed_step"):
        metric_reader("mixed_step_ms.chat", root)(Run())
