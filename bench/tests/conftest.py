"""CPU tests of the benchmark harness.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They run the harness below its look for a chip, on the CPU, with cells
added from new files in a copy of the benchmark.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

# tiny sizes, same families and key names as the real configurations
TINY = {
    "smollm-360m": dict(
        program={"n_layers": 2, "d_model": 64, "n_heads": 4,
                 "n_kv_heads": 2, "d_ff": 128, "vocab_size": 512},
        config={"num_hidden_layers": 2, "hidden_size": 64,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "intermediate_size": 128, "vocab_size": 512}),
}


def add_tiny_cell(root: str, name: str, base_config: str, base_traffic: str,
                  limit: float = 0.05) -> str:
    """Add a cell ``name`` to the benchmark copied at ``root`` from new
    files only: a configuration cut from ``base_config``, a mix cut
    from ``base_traffic``, its limits, and new BENCHMARK.json entries.
    Returns the cell's name."""
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(root, "bench", "configs",
                                      base_config + ".json")))
    t = TINY[base_config]
    cfg["name"] = f"{name}-config"
    cfg["program"] = {**cfg.get("program", {}), **t["program"]}
    cfg["config"] = {**cfg["config"], **t["config"]}
    cfg["engine"].update(batch_size=8, capacity=256, num_blocks=None)
    cfg["server"]["workers"] = 8
    cfg_file = f"bench/configs/{name}-config.json"
    json.dump(cfg, open(os.path.join(root, cfg_file), "w"))
    mix = json.load(open(os.path.join(root, "bench", "traffic",
                                      base_traffic + ".json")))
    mix["prompt"].update(min=8, max=96)
    if "median" in mix["prompt"]:
        mix["prompt"]["median"] = 40
    mix["max_new"] = 16
    if mix["loop"] == "open":
        mix.update(rate_rps=4.0, lead_in_s=1.0, grace_s=30.0)
    else:
        mix["concurrency"] = 16
    json.dump(mix, open(os.path.join(root, "bench", "traffic",
                                     f"{name}-mix.json"), "w"))
    json.dump({"served_gap": limit},
              open(os.path.join(root, "bench", "limits", name + ".json"), "w"))
    b["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": cfg_file, "reduced": cfg["reduced"],
                         "why": "tiny copy for the CPU tests"})
    b["workloads"].append({"name": name, "config": cfg["name"],
                           "traffic": f"{name}-mix",
                           "chips": cfg["chips"], "why": "CPU test"})
    # the new cell reports what the cell of the same mix reports
    base = next(w["name"] for w in b["workloads"]
                if w["traffic"] == base_traffic)
    for m in b["end_to_end"] + b["per_layer"]:
        if base in m.get("workloads", []):
            m["workloads"].append(name)
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return name


@pytest.fixture
def bench_root(tmp_path):
    """A copy of the benchmark (BENCHMARK.json and bench/), to which a
    test adds files."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def run_cell(root: str, name: str, seed: int = 3, seconds: float = 3.0,
             trace: bool = False, control: bool = False):
    """One run of cell ``name`` through the harness's loading path,
    below its look for a chip."""
    import time
    import jax
    from harness.cell import run
    from harness.load import load_cell
    cell = load_cell(name, root)
    return run(cell, seed, seconds, trace, t_process=time.monotonic(),
               devices=jax.devices()[:max(1, cell.chips)], control=control)
