import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the dry-run analyses compiled artifacts on fake host devices; never let
# jax grab a real accelerator (libtpu init hangs on non-TPU hosts)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run (brief deliverable (e)): lower + compile every
(architecture x input shape) on the production meshes and extract the
roofline inputs.

For each cell this script:
  1. builds the (16,16) single-pod or (2,16,16) multi-pod mesh,
  2. lowers the right step (train_step / prefill_step / serve_step) with
     full in/out shardings from ``launch.sharding``,
  3. compiles, records ``memory_analysis()`` + ``cost_analysis()``,
  4. parses the compiled HLO for collective ops and sums their bytes,
  5. writes one JSON artifact under benchmarks/artifacts/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--arch ...]
"""
import argparse
import json
import math
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_configs, get_config, skip_reason
from repro.launch import sharding as rules
from repro.launch.mesh import data_axes, make_production_mesh
from repro.models import build_model
from repro.models.layers import set_activation_sharding
from repro.train import OptConfig, make_train_step
from repro.train.optimizer import init_opt_state

ARTIFACT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__)))),
    "benchmarks", "artifacts", "dryrun",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string, incl. tuples '(bf16[2,3], f32[4])'."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the HLO."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w\.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", line)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-start"):
            op = op[:-6]
        if op in out:
            out[op]["count"] += 1
            out[op]["bytes"] += _shape_bytes(m.group(1))
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def input_specs(cfg, spec, n_patch: int = 256):
    """ShapeDtypeStruct stand-ins for the model inputs of one shape cell."""
    b, s = spec.global_batch, spec.seq_len
    sds = jax.ShapeDtypeStruct
    if spec.kind in ("train", "prefill"):
        if cfg.family == "audio":
            return {
                "prefix_embeds": sds((b, s, cfg.d_model), jnp.bfloat16),
                "labels": sds((b, s), jnp.int32),
            }
        batch = {"tokens": sds((b, s - (n_patch if cfg.family == "vlm" else 0)), jnp.int32)}
        if cfg.family == "vlm":
            batch["prefix_embeds"] = sds((b, n_patch, cfg.d_model), jnp.bfloat16)
        return batch
    # decode: one new token against a seq_len-deep cache
    return {"tokens": sds((b, 1), jnp.int32)}


def _moe_kwargs(cfg, spec, extra_slots, model_size=16):
    if cfg.family != "moe":
        return {}
    pad = -(-cfg.n_experts // model_size) * model_size  # round up to tile TP
    cf_train = float(os.environ.get("REPRO_CAPACITY_FACTOR", "1.25"))
    return {
        "extra_slots": extra_slots,
        "capacity_factor": cf_train if spec.kind == "train" else 2.0,
        "expert_pad": pad if pad != cfg.n_experts else 0,
    }


def build_cell(arch: str, shape: str, multi_pod: bool, extra_slots: int = 16):
    """Returns (step_fn, example_args, in_shardings, out_shardings, donate)."""
    cfg = get_config(arch)
    spec = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = data_axes(multi_pod)
    model_size = mesh.shape["model"]
    data_size = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    model = build_model(cfg)

    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    fsdp = int(os.environ.get("REPRO_FSDP", "1"))
    p_spec = rules.param_specs(
        params_shape, model_size, data_size=mesh.shape["data"] if fsdp else 1
    )
    p_shard = rules.named(mesh, p_spec)

    batch_shape = input_specs(cfg, spec)
    b_spec = rules.batch_specs(batch_shape, dp)
    b_shard = rules.named(mesh, b_spec)

    axis_sizes = dict(mesh.shape)
    set_activation_sharding(P(dp, "model", None), axis_sizes)

    dp_total = int(np.prod([mesh.shape[a] for a in dp]))

    def logits_sharding(batch_dim: int) -> NamedSharding:
        b_ax = dp if batch_dim % dp_total == 0 and batch_dim > 1 else None
        v_ax = "model" if cfg.vocab % model_size == 0 else None
        return NamedSharding(mesh, P(b_ax, v_ax))

    mkw = _moe_kwargs(cfg, spec, extra_slots, model_size)

    if spec.kind == "train":
        opt_shape = jax.eval_shape(lambda p: init_opt_state(p), params_shape)
        o_spec = rules.opt_specs(p_spec, params_shape, data_size)
        # moment specs computed for m; reuse for v; step scalar replicated
        o_spec = {"m": o_spec["m"], "v": o_spec["v"], "step": P()}
        o_shard = rules.named(mesh, o_spec)
        opt_cfg = OptConfig()
        step = make_train_step(model, opt_cfg, loss_kwargs=mkw)
        args = (params_shape, opt_shape, batch_shape)
        in_sh = (p_shard, o_shard, b_shard)
        out_sh = (p_shard, o_shard, {"grad_norm": NamedSharding(mesh, P()),
                                     "lr": NamedSharding(mesh, P()),
                                     "loss": NamedSharding(mesh, P())})
        return mesh, step, args, in_sh, out_sh, (0, 1)

    if spec.kind == "prefill":
        def prefill_step(params, batch):
            out = model.forward_hidden(params, batch, dtype=jnp.bfloat16, remat=False)
            h = out[0] if isinstance(out, tuple) else out
            if cfg.family == "ssm":
                table = params["lm_head"]["w"].T
            else:
                from repro.models.transformer import logits_table

                table = logits_table(cfg, params)
            return (h[:, -1, :] @ table.T.astype(h.dtype)).astype(jnp.float32)

        args = (params_shape, batch_shape)
        in_sh = (p_shard, b_shard)
        out_sh = logits_sharding(spec.global_batch)
        return mesh, prefill_step, args, in_sh, out_sh, ()

    # decode
    cache_shape = jax.eval_shape(
        lambda: model.init_cache(spec.global_batch, spec.seq_len)
    )
    c_spec = rules.cache_specs(cache_shape, dp, model_size)
    c_shard = rules.named(mesh, c_spec)
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, cache, batch, pos):
        return model.decode_step(params, cache, batch["tokens"], pos, **mkw)

    args = (params_shape, cache_shape, batch_shape, pos_shape)
    in_sh = (p_shard, c_shard, b_shard, NamedSharding(mesh, P()))
    out_sh = (logits_sharding(spec.global_batch), c_shard)
    return mesh, serve_step, args, in_sh, out_sh, (1,)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str, extra_slots: int = 16) -> dict:
    cfg = get_config(arch)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok"}
    reason = skip_reason(cfg, shape)
    if reason:
        record.update(status="skipped", reason=reason)
        _write(out_dir, record)
        return record
    t0 = time.time()
    try:
        mesh, step, args, in_sh, out_sh, donate = build_cell(
            arch, shape, multi_pod, extra_slots
        )
        with mesh:
            jitted = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donate,
            )
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            text = compiled.as_text()
        from repro.launch.hlo_analysis import analyze

        deep = analyze(text)  # trip-count-aware per-device costs
        record.update(
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            hlo_bytes=len(text),
            # raw XLA numbers (loop bodies counted once — kept for reference)
            xla_flops=float(cost.get("flops", -1)) if cost else -1,
            xla_bytes_accessed=float(cost.get("bytes accessed", -1)) if cost else -1,
            # trip-count-aware per-device analysis (the roofline inputs)
            flops=deep["flops"],
            hbm_bytes=deep["hbm_bytes"],
            collectives=deep["collectives"],
            collective_payload_bytes=deep["collective_payload_bytes"],
            collective_wire_bytes=deep["collective_wire_bytes"],
            memory=_memory_dict(mem),
            n_devices=int(np.prod(list(mesh.shape.values()))),
        )
    except Exception as e:  # record the failure, don't kill the sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    finally:
        set_activation_sharding(None)
    _write(out_dir, record)
    return record


def _memory_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes", "host_argument_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(mem)[:2000]
    return out


def _write(out_dir: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{record['mesh']}__{record['arch']}__{record['shape']}.json"
    )
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    status = record["status"]
    extra = ""
    if status == "ok":
        extra = (
            f" flops/dev={record['flops']:.3e}"
            f" hbm/dev={record['hbm_bytes']:.3e}"
            f" wire/dev={record['collective_wire_bytes']:.3e}"
            f" compile={record['compile_s']}s"
        )
    elif status == "error":
        extra = " " + record["error"][:200]
    print(f"[dryrun] {record['mesh']} {record['arch']} {record['shape']}: {status}{extra}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--extra-slots", type=int, default=16)
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    archs = args.arch or (sorted(all_configs()) if args.all else None)
    shapes = args.shape or (list(SHAPES) if args.all else None)
    if not archs or not shapes:
        ap.error("pass --arch/--shape or --all")
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mp, args.out, args.extra_slots)
                failures += rec["status"] == "error"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
