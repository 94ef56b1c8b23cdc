"""Launch layer: sharding rules, HLO analysis, dry-run cell, elastic restart."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import HloModule, analyze
from repro.launch.sharding import batch_specs, cache_specs, opt_specs, param_specs

_REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ sharding rules
def test_param_specs_suffix_rules():
    params = {
        "embed": {"table": jnp.zeros((256000, 128))},
        "blocks": {
            "attn": {"wq": jnp.zeros((4, 128, 256)), "wo": jnp.zeros((4, 256, 128))},
            "mlp": {"w_up": jnp.zeros((4, 128, 512)), "w_down": jnp.zeros((4, 512, 128))},
            "experts": {"w_gate": jnp.zeros((4, 32, 128, 64))},
            "ln1": {"scale": jnp.zeros((128,))},
        },
    }
    specs = param_specs(params, model_size=16)
    assert specs["embed"]["table"] == P("model", None)
    assert specs["blocks"]["attn"]["wq"] == P(None, None, "model")
    assert specs["blocks"]["attn"]["wo"] == P(None, "model", None)
    assert specs["blocks"]["mlp"]["w_up"] == P(None, None, "model")
    assert specs["blocks"]["mlp"]["w_down"] == P(None, "model", None)
    assert specs["blocks"]["experts"]["w_gate"] == P("model", None, None, None) or \
        specs["blocks"]["experts"]["w_gate"] == P(None, "model", None, None)
    assert specs["blocks"]["ln1"]["scale"] == P()


def test_param_specs_indivisible_replicates():
    params = {"lm_head": {"w": jnp.zeros((128, 49155))}}  # 49155 % 16 != 0
    specs = param_specs(params, model_size=16)
    # falls back: vocab not divisible -> d gets sharded or replicated, never crash
    assert isinstance(specs["lm_head"]["w"], P)


def test_opt_specs_zero1_shards_replicated_moments():
    params = {"big": jnp.zeros((1 << 11, 1 << 10))}  # 2M elems, replicated spec
    p_spec = {"big": P()}
    o = opt_specs(p_spec, params, data_size=16, zero1=True)
    assert o["m"]["big"] == P("data", None)
    o2 = opt_specs(p_spec, params, data_size=16, zero1=False)
    assert o2["m"]["big"] == P()


def test_batch_and_cache_specs():
    b = batch_specs({"tokens": jnp.zeros((32, 128), jnp.int32)}, ("data",))
    assert b["tokens"] == P(("data",), None)
    cache = {"k": jnp.zeros((4, 32, 16, 1024, 128))}  # [L,B,H,S,hd]
    c = cache_specs(cache, ("data",), model_size=16)
    assert c["k"] == P(None, ("data",), "model", None, None)
    # B=1 long-context: shard sequence instead
    cache1 = {"k": jnp.zeros((4, 1, 4, 524288, 128))}
    c1 = cache_specs(cache1, ("data",), model_size=16)
    assert c1["k"][3] in ("data", ("data",))


# -------------------------------------------------------------- hlo analysis
_TOY_HLO = """
HloModule toy

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %dotx = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%dotx), replica_groups=[4,2]<=[8], to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i2, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_hlo_analysis_trip_count_multiplies():
    res = analyze(_TOY_HLO)
    # one 8x8x8 matmul per iteration, 10 iterations
    assert res["flops"] == pytest.approx(10 * 2 * 8 * 8 * 8, rel=0.2)
    ar = res["collectives"]["all-reduce"]
    assert ar["count"] == 10
    assert ar["payload_bytes"] == 10 * 8 * 8 * 4
    # ring factor for group size 2: 2*(2-1)/2 = 1.0
    assert ar["wire_bytes"] == pytest.approx(10 * 8 * 8 * 4 * 1.0)


def test_hlo_analysis_handles_tuple_shapes():
    mod = HloModule(_TOY_HLO)
    assert mod.entry == "main"
    assert "body" in mod.computations


# ------------------------------------------------------------ compile cache
@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, from_env):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to the
    fixed checkout path; small kernels are cached too."""
    from repro.launch import compile_cache

    updates = {}
    monkeypatch.setattr(
        compile_cache.jax.config, "update", lambda k, v: updates.update({k: v})
    )
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(_REPO / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0


# ------------------------------------------------------------- dry-run cell
def test_dryrun_single_cell_subprocess(tmp_path):
    """One real dry-run cell end to end (512 fake devices, 16x16 mesh)."""
    code = (
        "import sys; sys.argv=['x','--arch','olmo-1b','--shape','train_4k',"
        f"'--out','{tmp_path}'];"
        "from repro.launch import dryrun; dryrun.main()"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": "cpu",
        },
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json, os

    files = os.listdir(tmp_path)
    assert len(files) == 1
    rec = json.load(open(tmp_path / files[0]))
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["flops"] > 1e13  # trip-count-aware, not body-once
    assert rec["collective_wire_bytes"] > 0


# ------------------------------------------------------------ elastic restart
_ELASTIC_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.launch.sharding import param_specs, named
from repro.train import (OptConfig, init_train_state, make_train_step,
                         save_checkpoint, load_checkpoint, restore_tree)
from repro.train.elastic import plan_mesh_shape

CKPT = os.environ["ELASTIC_CKPT"]
cfg = get_config("olmo-1b").reduced()
model = build_model(cfg)
opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
step = jax.jit(make_train_step(model, opt))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}

# phase 1: mesh (4 data, 2 model)
mesh1 = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params, opt_state = init_train_state(model, jax.random.PRNGKey(0))
p_spec = param_specs(params, model_size=2)
with mesh1:
    params = jax.device_put(params, named(mesh1, p_spec))
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
loss_before = float(m["loss"])
save_checkpoint(CKPT, 2, {"params": params, "opt": opt_state})

# phase 2: "lose" half the devices -> mesh (2 data, 2 model); resharding restore
plan = plan_mesh_shape(4, model_parallel=2, chips_per_pod=8)
assert plan.model == 2 and plan.data * plan.model <= 4
mesh2 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
_, flat = load_checkpoint(CKPT)
with mesh2:
    tree = restore_tree({"params": params, "opt": opt_state}, flat,
                        {"params": named(mesh2, p_spec),
                         "opt": jax.tree.map(lambda _: NamedSharding(mesh2, P()), opt_state)})
    p2, o2 = tree["params"], tree["opt"]
    p2, o2, m2 = step(p2, o2, batch)
assert np.isfinite(float(m2["loss"]))
print("ELASTIC_OK", loss_before, float(m2["loss"]))
"""


def test_elastic_shrink_restart_subprocess(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _ELASTIC_SNIPPET],
        capture_output=True, text=True, timeout=900,
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": "cpu",
            "ELASTIC_CKPT": str(tmp_path / "ckpt"),
        },
        cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ELASTIC_OK" in proc.stdout
