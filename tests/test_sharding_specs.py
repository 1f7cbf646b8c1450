"""Sharding-rule unit tests + a small-mesh dry-run smoke (subprocess:
the host device count flag must precede jax init)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build_model
from repro.models.sharding import cache_specs, paged_cache_specs, param_specs


def _leaves_with_paths(tree):
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_param_specs_match_rank_and_rules():
    cfg = get_config("glm4-9b", smoke=True)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, dp=("data",))
    shape_leaves = _leaves_with_paths(shapes)
    spec_leaves = _leaves_with_paths(specs)
    for path, spec in spec_leaves.items():
        assert len(spec) <= shape_leaves[path].ndim, path
    # spot checks
    assert spec_leaves["embed"] == P("model", "data")
    assert spec_leaves["blocks/s0/attn/wq"] == P(None, "data", "model")
    assert spec_leaves["blocks/s0/mlp/w_down"] == P(None, "model", "data")


@pytest.mark.parametrize("arch", ["glm4-9b", "qwen2.5-32b", "dbrx-132b",
                                  "xlstm-350m"])
def test_param_specs_rank_invariant_across_configs(arch):
    """Every config's spec tree must stay within leaf ranks (eval_shape
    only — no compilation), so new architectures can't silently ship
    rules that over-index their parameters."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, dp=("data",))
    shape_leaves = _leaves_with_paths(shapes)
    for path, spec in _leaves_with_paths(specs).items():
        assert len(spec) <= shape_leaves[path].ndim, path


def test_param_specs_divisibility_filter():
    cfg = get_config("whisper-tiny", smoke=False)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, dp=("data",),
                        axis_sizes={"data": 16, "model": 16})
    leaves = _leaves_with_paths(specs)
    # vocab 51865 is not divisible by 16 -> model axis dropped from embed
    assert leaves["embed"][0] is None


def test_moe_expert_parallel_specs():
    cfg = get_config("dbrx-132b", smoke=True)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, dp=("data",))
    leaves = _leaves_with_paths(specs)
    assert leaves["blocks/s0/moe/w_gate"][1] == "model"  # experts on TP axis


def test_cache_specs_batch1_shards_sequence():
    cfg = get_config("glm4-9b", smoke=True)
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(1, 512))
    specs = cache_specs(cache, dp=("data",), shard_seq_when_batch1=True)
    k_spec = specs["blocks"]["s0"]["k"]
    assert k_spec[2] == "data"  # sequence dim sharded for batch-1


def test_cache_specs_batched_decode_shards_batch():
    cfg = get_config("glm4-9b", smoke=True)
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 512))
    specs = cache_specs(cache, dp=("data",), shard_seq_when_batch1=False)
    k_spec = specs["blocks"]["s0"]["k"]
    assert k_spec[1] == "data"


def _paged_struct(family):
    from conftest import FAMILY_CFGS
    model = build_model(FAMILY_CFGS[family])
    return jax.eval_shape(
        lambda: model.init_paged_cache(8, 4, num_state_slots=4))


@pytest.mark.parametrize("family",
                         ["transformer", "mamba", "xlstm", "hybrid"])
def test_paged_cache_specs_pool_axis_replicated(family):
    """The serving pool's block/slot axis must never shard: pages are
    addressed by host-side tables, so every device needs every block
    resident.  TP lives on feature dims only."""
    cache = _paged_struct(family)
    shape_leaves = _leaves_with_paths(cache)
    for path, spec in _leaves_with_paths(
            paged_cache_specs(cache)).items():
        assert len(spec) <= shape_leaves[path].ndim, path
        lead = 1 if path.startswith("blocks") or "blocks/" in path else 0
        if shape_leaves[path].ndim > lead:
            assert spec[lead] is None, \
                f"{family}:{path} shards the block/slot axis"


def test_paged_cache_specs_kv_sharded_on_head_dim():
    cache = _paged_struct("transformer")
    leaves = _leaves_with_paths(paged_cache_specs(cache))
    k = next(v for p, v in leaves.items() if p.endswith("/k"))
    assert k[-1] == "model"  # (nb, KV, rows, lanes): head_dim on TP axis


def test_paged_cache_specs_divisibility_filter():
    # TINY_SERVE head_dim is 8: a 16-way model axis can't divide it, so
    # the filter must drop the axis rather than emit an invalid layout
    cache = _paged_struct("transformer")
    leaves = _leaves_with_paths(
        paged_cache_specs(cache, axis_sizes={"model": 16}))
    k = next(v for p, v in leaves.items() if p.endswith("/k"))
    assert all(a is None for a in k)


def test_paged_cache_structs_and_shardings_helper():
    """The launch-layer helper mirrors the pool struct one-to-one with
    NamedShardings (works on any device count — (1,1) mesh here)."""
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.specs import paged_cache_structs_and_shardings
    from conftest import FAMILY_CFGS
    model = build_model(FAMILY_CFGS["hybrid"])
    mesh = make_serving_mesh(model=1)
    struct, shardings = paged_cache_structs_and_shardings(
        model, mesh, num_blocks=8, block_size=4, num_state_slots=4)
    assert (jax.tree_util.tree_structure(struct)
            == jax.tree_util.tree_structure(shardings))
    from jax.sharding import NamedSharding
    assert all(isinstance(s, NamedSharding)
               for s in jax.tree_util.tree_leaves(shardings))


DRYRUN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.models.sharding import param_specs
from repro.training import TrainState, make_train_step
from repro.optim import adamw_init

dp, tp = {mesh}
mesh = jax.make_mesh((dp, tp), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("{arch}", smoke=True)
model = build_model(cfg, remat=True)
params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
pspecs = param_specs(params_s, dp=("data",),
                     axis_sizes={{"data": dp, "model": tp}})
state_s = jax.eval_shape(lambda p: TrainState(p, adamw_init(p)), params_s)
state_specs = TrainState(params=pspecs,
                         opt=type(state_s.opt)(step=P(), m=pspecs, v=pspecs))
state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs)
batch = {{"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
          "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}}
batch_sh = {{k: NamedSharding(mesh, P("data", None)) for k in batch}}
step = make_train_step(model)
with mesh:
    lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(state_s, batch)
    compiled = lowered.compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):  # older jax returns one dict per device
    ca = ca[0]
print("COMPILED_OK", ca.get("flops", 0) > 0)
"""


def _run_dryrun(n_dev, mesh, arch):
    env = dict(os.environ, PYTHONPATH="src")
    src = DRYRUN_SMOKE.format(n_dev=n_dev, mesh=mesh, arch=arch)
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "COMPILED_OK True" in out.stdout, out.stdout + out.stderr


def test_dryrun_smoke_on_4_host_devices():
    _run_dryrun(4, (2, 2), "smollm-360m")


@pytest.mark.parametrize("n_dev,mesh,arch", [
    (1, (1, 1), "smollm-360m"),    # degenerate mesh must still compile
    (2, (1, 2), "smollm-360m"),    # pure tensor parallel
    (2, (2, 1), "glm4-9b"),        # pure data parallel, second config
    (8, (2, 4), "smollm-360m"),    # 8-host mixed
    (8, (4, 2), "qwen2.5-32b"),    # 8-host, dp-heavy, third config
])
def test_dryrun_mesh_sweep(n_dev, mesh, arch):
    _run_dryrun(n_dev, mesh, arch)
