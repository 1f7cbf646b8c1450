"""``correct`` on tiny cells, driven through the whole harness below its
look for a chip: sound runs pass, and the same verdict fails the float8
control put in the program's place and a token altered where it is
produced."""
import numpy as np
import pytest

from conftest import add_tiny_cell, run_cell

# the tiny cells' limit, between their readings: on the tiny cells
# (tied head, logits of about 0.16 spread) sound runs read 0.00004-0.008
# (bf16 program against the float32 reference), the float8 control
# 0.05-0.10
LIMIT = 0.025


# each tiny cell mirrors the cell of the same mix, and reports its
# end-to-end metrics
MIXES = {"chat-poisson": {"ttft_p95_ms", "tpot_p95_ms", "setup_s"},
         "longctx-closed": {"tokens_per_s", "tpot_p95_ms", "setup_s"}}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_cell_added_from_new_files_runs(bench_root, mix):
    name = add_tiny_cell(bench_root, "tiny-" + mix, "smollm-360m", mix,
                         LIMIT)
    out = run_cell(bench_root, name, seed=2 ** 31 + 99, control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == MIXES[mix]
    c = out["checks"]
    assert c["served_gap"]["value"] <= LIMIT
    assert c["unsampled"]["value"] == 0
    # the control: the reference in float8 in the program's place,
    # judged by the same limits, is not correct
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["served_gap"]["value"] > LIMIT
    assert (ctl["checks"]["served_gap"]["value"]
            > 3 * max(c["served_gap"]["value"], 1e-3))
    assert list(out)[-1] == "checks"


def test_closed_loop_and_traced_run(bench_root):
    """The generator's closed loop, from a mix file that asks for it."""
    import json
    import os
    name = add_tiny_cell(bench_root, "tiny-closed", "smollm-360m",
                         "chat-poisson", LIMIT)
    path = os.path.join(bench_root, "bench", "traffic", name + "-mix.json")
    mix = json.load(open(path))
    mix.update(loop="closed", concurrency=16)
    json.dump(mix, open(path, "w"))
    out = run_cell(bench_root, name, seed=5, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    # no device planes on the CPU: device-trace metrics are left out,
    # counter metrics are there
    assert set(m) == {"host_syncs_per_step.chat"}
    assert m["host_syncs_per_step.chat"]["value"] > 0
    assert out["device"]["window_s"] > 0


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_altered_token_is_caught(bench_root, mix, monkeypatch):
    from repro.serving.engine import ServeEngine
    drain = ServeEngine._drain_burst

    def altered(self, tok_buf, *a, **kw):
        toks = np.asarray(tok_buf).copy()
        toks[0, :] = (toks[0, :] + 1) % self.model.cfg.vocab_size
        return drain(self, toks, *a, **kw)
    monkeypatch.setattr(ServeEngine, "_drain_burst", altered)
    name = add_tiny_cell(bench_root, "tiny-" + mix, "smollm-360m", mix,
                         LIMIT)
    out = run_cell(bench_root, name, seed=11)
    assert not out["correct"]
    assert out["checks"]["served_gap"]["value"] > LIMIT
