"""The readers of the server's per-request timing records, on tiny
traced cells driven through the whole harness below its look for a
chip."""
import pytest

from conftest import add_tiny_cell, run_cell

CHAT = ("queue_wait_p95_ms.chat", "prefill_p95_ms.chat",
        "frontdoor_p95_ms.chat")


@pytest.fixture
def seen_run(monkeypatch):
    """The harness's ``Run`` of the last traced run, as its metric
    readers saw it."""
    import harness.cell
    seen = {}
    reader = harness.cell.metric_reader

    def keep(name, root):
        read = reader(name, root)

        def wrapped(run):
            seen["run"] = run
            return read(run)
        return wrapped
    monkeypatch.setattr(harness.cell, "metric_reader", keep)
    return seen


def test_open_loop_chat_cell_splits_its_ttft(bench_root, seen_run):
    name = add_tiny_cell(bench_root, "tiny-spans-chat", "smollm-360m",
                         "chat-poisson", 0.025)
    out = run_cell(bench_root, name, seed=2 ** 31 + 7, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    for k in CHAT:
        assert k in m and m[k]["unit"] == "ms" and m[k]["value"] >= 0, k
    assert "done_hold_p95_ms.decode" not in m
    run = seen_run["run"]
    timed = [r for r in run.scored()
             if getattr(r.res, "server_timing", None) is not None]
    assert timed and len(timed) == sum(1 for r in run.scored() if r.ok)
    for r in timed:
        t = r.res.server_timing
        # the server's share of the request's time to first token lies
        # inside the client's, which runs from the request's due time
        assert t["queue"] + t["prefill"] <= r.res.t_first - r.due
        assert t["ingress"] + t["queue"] + t["prefill"] \
            <= r.res.t_first - r.res.t_submit


def test_closed_loop_longctx_cell_reports_the_done_hold(bench_root):
    name = add_tiny_cell(bench_root, "tiny-spans-long", "smollm-360m",
                         "longctx-closed", 0.025)
    out = run_cell(bench_root, name, seed=2 ** 31 + 8, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["done_hold_p95_ms.decode"]["value"] >= 0
    # the TTFT tail is the open loop's alone, and so is its split
    assert not set(CHAT) & set(m)
