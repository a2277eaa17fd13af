import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production mesh using ShapeDtypeStruct stand-ins (no
allocation), then record memory / cost / collective analysis for §Dry-run
and §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The XLA_FLAGS line above MUST precede every jax-touching import: jax locks
the device count at first backend init. Everything else (tests, benches)
sees the single real CPU device.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, cells, get_arch
from repro.distributed.context import mesh_context
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import ShardingRules, as_sds, to_named
from repro.launch.specs import (batch_shapes, cache_shapes, opt_shapes,
                                params_shapes)
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models.lm import _attn_layout
from repro.optim import AdamWConfig

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def lower_snn(multi_pod: bool):
    """Dry-run the paper's own full-scale config: 160M neurons / 40B+
    synapses, hierarchically routed (core/distributed_engine.py)."""
    from repro.core.distributed_engine import (SNNShardConfig,
                                               make_snn_step,
                                               snn_shardings,
                                               snn_state_shapes)
    cfg = SNNShardConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh_context(mesh):
        shapes = snn_state_shapes(cfg, mesh)
        sh = snn_shardings(cfg, mesh)
        sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
               for k, v in shapes.items()}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step = make_snn_step(cfg, mesh)
        t0 = time.time()
        jfn = jax.jit(step, out_shardings=sh, donate_argnums=(0,))
        lowered = jfn.lower(sds, key)
        compiled = lowered.compile()
        t_compile = time.time() - t0
    text = compiled.as_text()
    an = hlo_analysis.analyze(text)
    mem = compiled.memory_analysis()
    return {
        "arch": "hiaer_snn_40b", "shape": "step_160M_40B",
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "variant": "baseline", "kind": "snn_step",
        "n_devices": mesh.devices.size,
        "n_neurons": cfg.n_neurons,
        "n_synapse_slots": cfg.fan_window_blocks * cfg.block * cfg.n_neurons,
        "analysis": an,
        "collectives": hlo_analysis.collective_breakdown(text),
        "memory": {k: int(getattr(mem, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes")},
        "compile_s": round(t_compile, 2), "lower_s": 0.0,
        "layout": "hiaer", "seq_len": 1, "global_batch": 1,
    }


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    """Lower + compile one cell; returns the result record dict."""
    if arch_id == "hiaer_snn_40b":
        return lower_snn(multi_pod)
    cfg = get_arch(arch_id)
    microbatches = 1
    if variant != "baseline":
        for v in variant.split("+"):
            if v.startswith("mb"):
                microbatches = int(v[2:])
        variant_cfg = "+".join(v for v in variant.split("+")
                               if not v.startswith("mb"))
        if variant_cfg:
            cfg = apply_variant(cfg, variant_cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    oc = AdamWConfig(moment_dtype=cfg.opt_dtype)
    t0 = time.time()
    with mesh_context(mesh):
        layout = _attn_layout(cfg, mesh.shape["model"])
        rules = ShardingRules(cfg, mesh, layout)
        p_shapes = params_shapes(cfg)
        p_specs = rules.params_specs(p_shapes)
        p_sh = to_named(p_specs, mesh)
        p_sds = as_sds(p_shapes, p_sh)
        b_shapes = batch_shapes(cfg, shape)
        b_sh = to_named(rules.batch_specs(b_shapes), mesh)
        b_sds = as_sds(b_shapes, b_sh)

        if shape.kind == "train":
            o_shapes = opt_shapes(cfg, oc)
            o_specs = rules.opt_specs(p_shapes, p_specs)
            o_sh = to_named(o_specs, mesh)
            o_sds = as_sds(o_shapes, o_sh)
            fn = make_train_step(cfg, oc, layout=layout,
                                 microbatches=microbatches)
            jfn = jax.jit(fn, out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(p_sds, o_sds, b_sds)
        elif shape.kind == "prefill":
            fn = make_prefill_step(cfg, layout=layout)
            c_shapes = cache_shapes(cfg, shape)
            c_sh = to_named(rules.cache_specs(c_shapes), mesh)
            jfn = jax.jit(fn, out_shardings=(None, c_sh))
            lowered = jfn.lower(p_sds, b_sds)
        else:  # decode
            fn = make_decode_step(cfg, layout=layout)
            c_shapes = cache_shapes(cfg, shape)
            c_sh = to_named(rules.cache_specs(c_shapes), mesh)
            c_sds = as_sds(c_shapes, c_sh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jfn = jax.jit(fn, out_shardings=(None, c_sh),
                          donate_argnums=(2,))
            lowered = jfn.lower(p_sds, b_sds["tokens"], c_sds, pos)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    text = compiled.as_text()
    an = hlo_analysis.analyze(text)
    ca = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        memd = {k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes")}
    except Exception as e:          # backend without memory analysis
        memd = {"error": str(e)}
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "variant": variant,
        "n_devices": mesh.devices.size,
        "layout": layout,
        "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "analysis": an,
        "collectives": hlo_analysis.collective_breakdown(text),
        "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed")},
        "memory": memd,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    return rec


def apply_variant(cfg, variant: str):
    """Named beyond-baseline variants used by §Perf hillclimbing."""
    import dataclasses
    parts = variant.split("+")
    for v in parts:
        if v == "hier_a2a" and cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, hierarchical_a2a=True))
        elif v == "sm_attn":
            cfg = dataclasses.replace(cfg, attn_impl="shardmap")
        elif v == "seqpar":
            cfg = dataclasses.replace(cfg, seq_parallel=True)
        elif v == "loss_chunk_2k":
            cfg = dataclasses.replace(cfg, loss_chunk=2048)
        elif v.startswith("capacity_"):
            f = float(v.split("_")[1])
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=f))
        elif v == "remat_dots":
            cfg = dataclasses.replace(cfg, remat_policy="dots")
        elif v.startswith("remat_"):
            cfg = dataclasses.replace(cfg, remat=v == "remat_on")
        else:
            raise ValueError(f"unknown variant {v}")
    return cfg


def run(arch_id, shape_name, multi_pod, out_dir: Path, variant="baseline",
        force=False):
    tag = "multi" if multi_pod else "single"
    name = f"{arch_id}__{shape_name}__{tag}"
    if variant != "baseline":
        name += f"__{variant}"
    path = out_dir / f"{name}.json"
    if path.exists() and not force:
        print(f"[skip] {name} (artifact exists)")
        return json.loads(path.read_text())
    print(f"[dryrun] {name} ...", flush=True)
    try:
        rec = lower_cell(arch_id, shape_name, multi_pod, variant)
        rec["status"] = "ok"
    except Exception as e:
        rec = {"arch": arch_id, "shape": shape_name, "mesh": tag,
               "variant": variant, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        print(f"[FAIL] {name}: {e}", flush=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1))
    if rec["status"] == "ok":
        a = rec["analysis"]
        print(f"[ok] {name}: compile={rec['compile_s']}s "
              f"flops={a['flops']:.3e} hbm={a['hbm_bytes_tight']:.3e} "
              f"coll={a['collective_bytes']:.3e} "
              f"temp={rec['memory'].get('temp_size_in_bytes', -1)/2**30:.2f}GiB",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        pairs = [(a, s.name) for a in ARCH_IDS for s in cells(a)]
        pairs.append(("hiaer_snn_40b", "step_160M_40B"))
    else:
        assert args.arch, "--arch required unless --all"
        if args.shape:
            pairs = [(args.arch, args.shape)]
        else:
            pairs = [(args.arch, s.name) for s in cells(args.arch)]
    n_ok = n_fail = 0
    for arch, shp in pairs:
        for mp in meshes:
            rec = run(arch, shp, mp, out_dir, variant=args.variant,
                      force=args.force)
            if rec.get("status") == "ok":
                n_ok += 1
            else:
                n_fail += 1
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
