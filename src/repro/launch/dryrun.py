import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " \
    + os.environ.get("XLA_FLAGS", "")

# ^^ MUST precede every other import (jax locks the device count on first
# init).  This file is the ONLY place the 512 placeholder devices exist;
# smoke tests and benchmarks see the real single device.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell and both production meshes —
(data=16, model=16) and (pod=2, data=16, model=16) — lower + compile the
train/prefill/serve step with ShapeDtypeStruct inputs (no allocation),
print ``memory_analysis()`` / ``cost_analysis()``, extract the roofline
terms, and persist everything to results/dryrun/*.json.  The DDMS field
cells (including the paper's 6-billion-vertex Fig. 17 example) go through
the same path with the shard_map pd-front program.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
        --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
    python -m repro.launch.dryrun --ddms paper_6b --mesh multi
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.registry import input_specs, shape_applicable
from repro.launch.mesh import (batch_axes_for, make_field_mesh,
                               make_production_mesh)
from repro.launch import roofline as RL
from repro.models import transformer as T
from repro.models.config import SHAPES
from repro.train import sharding as SH
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import (StepConfig, make_prefill_step,
                                    make_serve_step, make_train_step)

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if not isinstance(x, jax.ShapeDtypeStruct) else x, tree)


def _sds_of_spec(spec_tree, mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        spec_tree, pspec_tree)


def _build_lowered(cfg, shape, mesh, rules, step_cfg: StepConfig):
    """jit(...).lower(...) for one cell (any cfg variant)."""
    from repro.models.layers import PM
    meta = T.lm_meta(cfg)
    pspecs = SH.param_specs(meta, rules, mesh)
    params_abs = jax.tree_util.tree_map(
        lambda pm, ps: jax.ShapeDtypeStruct(
            pm.shape, jnp.float32, sharding=NamedSharding(mesh, ps)),
        meta, pspecs, is_leaf=lambda x: isinstance(x, PM))
    ins = input_specs(cfg, shape)
    SH.set_rules(rules, mesh)
    try:
        if shape.kind == "train":
            # optimizer m/v shard exactly like the params (ZeRO/FSDP)
            from repro.train.optimizer import OptState
            opt_abs = OptState(
                jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=NamedSharding(mesh, P())),
                params_abs, params_abs)
            bspec = {k: SH.batch_spec(rules, len(v.shape))
                     for k, v in ins.items()}
            batch_abs = _sds_of_spec(ins, mesh, bspec)
            fn = make_train_step(cfg, OptConfig(), step_cfg)
            return jax.jit(fn).lower(params_abs, opt_abs, batch_abs), meta
        if shape.kind == "prefill":
            bspec = {k: SH.batch_spec(rules, len(v.shape))
                     for k, v in ins.items()}
            batch_abs = _sds_of_spec(ins, mesh, bspec)
            fn = make_prefill_step(cfg)
            return jax.jit(fn).lower(params_abs, batch_abs["tokens"],
                                     batch_abs.get("frontend")), meta
        # decode
        cache_abs = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = SH.cache_specs(cfg, cache_abs, rules, mesh)
        cache_abs = jax.tree_util.tree_map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
            cache_abs, cspecs)
        tok_abs = jax.ShapeDtypeStruct(
            (shape.global_batch,), jnp.int32,
            sharding=NamedSharding(
                mesh, SH.batch_spec(rules, 1)
                if shape.global_batch % int(np.prod(
                    [mesh.shape[a] for a in rules.batch_axes])) == 0
                else P()))
        fn = make_serve_step(cfg)
        return jax.jit(fn).lower(params_abs, cache_abs, tok_abs), meta
    finally:
        SH.set_rules(None, None)


def _variant_layer_counts(cfg):
    if cfg.shared_attn_every:
        k = cfg.shared_attn_every
        return k, 2 * k
    return 2, 4


class _flash_exact:
    """Coarse flash tiles + unrolled kv scans so cost analysis is exact."""

    def __enter__(self):
        from repro.models import layers as L
        self.saved = (L.FLASH_QC, L.FLASH_KC, L.FLASH_UNROLL)
        L.FLASH_QC, L.FLASH_KC, L.FLASH_UNROLL = 2048, 4096, True

    def __exit__(self, *a):
        from repro.models import layers as L
        L.FLASH_QC, L.FLASH_KC, L.FLASH_UNROLL = self.saved


def _exact_costs(cfg, shape, mesh, rules, step_cfg):
    """XLA cost analysis counts while bodies once; recover exact per-step
    costs by compiling two *unrolled* reduced-depth variants and
    extrapolating linearly in layer count (EXPERIMENTS.md §Roofline)."""
    import dataclasses
    k1, k2 = _variant_layer_counts(cfg)
    meas = []
    for k in (k1, k2):
        ckw = dict(n_layers=k, unroll=True)
        if cfg.enc_dec:
            ckw["enc_layers"] = k
        cfgk = dataclasses.replace(cfg, **ckw)
        with _flash_exact():
            lowered, _ = _build_lowered(cfgk, shape, mesh, rules, step_cfg)
            compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        coll = RL.collective_bytes(compiled.as_text())
        meas.append((float(ca.get("flops", 0)),
                     float(ca.get("bytes accessed", 0)), coll))
    dk = k2 - k1

    def extrap(a, b):
        per = (b - a) / dk
        return max(0.0, a - k1 * per) + cfg.n_layers * per

    flops = extrap(meas[0][0], meas[1][0])
    byts = extrap(meas[0][1], meas[1][1])
    coll = {key: int(extrap(meas[0][2].get(key, 0), meas[1][2].get(key, 0)))
            for key in meas[0][2]}
    return flops, byts, coll, {"k1": k1, "k2": k2, "measured": meas}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               step_cfg: StepConfig = StepConfig(), rules_kw=None,
               exact: bool = True, mla_absorbed: bool = False):
    if mla_absorbed:
        from repro.models import layers as L
        L.MLA_ABSORBED_DECODE = True
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": "long_500k needs sub-quadratic attention "
                           "(see DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    rules = SH.ShardingRules(batch_axes=batch_axes_for(mesh),
                             **(rules_kw or {}))

    # ---- full-depth compile: validates SPMD + memory at scale ----------
    t0 = time.time()
    lowered, meta = _build_lowered(cfg, shape, mesh, rules, step_cfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
            print("memory_analysis:", mem or ma)
    except Exception as e:  # CPU backend may not implement it
        mem = {"error": str(e)}
        print("memory_analysis unavailable:", e)

    # ---- exact roofline costs via unrolled reduced-depth variants ------
    mf = RL.model_flops(cfg, shape, n_dev)
    if exact:
        try:
            flops, byts, coll, detail = _exact_costs(cfg, shape, mesh,
                                                     rules, step_cfg)
        except Exception as e:
            print("exact-cost pass failed, falling back to scan costs:", e)
            flops = byts = None
            coll = detail = None
    else:
        flops = byts = coll = detail = None
    roof_scan = RL.analyze(compiled, mf)
    if flops is not None:
        cbytes = sum(v for k, v in coll.items() if k in RL._COLLECTIVES)
        terms = dict(compute=flops / RL.PEAK_FLOPS,
                     memory=byts / RL.HBM_BW,
                     collective=cbytes / RL.ICI_BW)
        dominant = max(terms, key=terms.get)
        roof = RL.Roofline(flops, byts, coll, terms["compute"],
                           terms["memory"], terms["collective"], dominant,
                           mf, mf / max(flops, 1.0))
    else:
        roof = roof_scan
    print("roofline:", roof.summary())

    param_bytes = sum(
        int(np.prod(pm.shape)) * 4 for pm in jax.tree_util.tree_leaves(
            meta, is_leaf=lambda x: hasattr(x, "axes")))
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "lower_s": t_lower, "compile_s": t_compile,
        "flops_per_device": roof.flops,
        "bytes_per_device": roof.bytes_accessed,
        "collectives": roof.coll,
        "compute_s": roof.compute_s, "memory_s": roof.memory_s,
        "collective_s": roof.collective_s, "dominant": roof.dominant,
        "model_flops_per_device": mf, "useful_ratio": roof.useful_ratio,
        "scan_level_costs": {"flops": roof_scan.flops,
                             "bytes": roof_scan.bytes_accessed,
                             "collectives": roof_scan.coll},
        "exact_detail": detail,
        "memory_analysis": mem,
        "param_bytes_global": param_bytes,
        "param_bytes_per_device_fsdp": param_bytes // n_dev,
    }


DDMS_FIELDS = {
    # paper Fig. 17: Turbulent Channel Flow subset, ~6e9 vertices
    "paper_6b": (2048, 1920, 1536),
    # strong-scaling dataset size (paper Sec. VI-A)
    "strong_512": (512, 512, 512),
}


def lower_ddms(field: str, multi_pod: bool, crit_cap: int = 4096,
               ring_rotations: int = 2, gradient_chunk=262144,
               use_sample_sort: bool = True):
    from repro.distributed.shardmap_pipeline import (FrontConfig,
                                                     _front_out_specs,
                                                     front_device_fn)
    dims = DDMS_FIELDS[field]
    mesh = make_field_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    axes = tuple(mesh.axis_names)
    cfg = FrontConfig(dims, n_dev, axis_name=axes if len(axes) > 1
                      else axes[0],
                      crit_cap=crit_cap, ring_rotations=ring_rotations,
                      gradient_chunk=gradient_chunk,
                      use_sample_sort=use_sample_sort)
    spec_in = P(axes if len(axes) > 1 else axes[0])
    out_specs = {k: (P() if v == P() else spec_in)
                 for k, v in _front_out_specs().items()}

    fn = jax.shard_map(lambda f: front_device_fn(cfg, f), mesh=mesh,
                       in_specs=spec_in, out_specs=out_specs,
                       check_vma=False)
    nv = int(np.prod(dims))
    f_abs = jax.ShapeDtypeStruct((nv,), jnp.float32,
                                 sharding=NamedSharding(mesh, spec_in))
    t0 = time.time()
    lowered = jax.jit(fn).lower(f_abs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
        print("memory_analysis:", mem or ma)
    except Exception as e:
        mem = {"error": str(e)}
    # useful work model: the gradient visits each vertex's 74-row star with
    # ~75 masked-argmin iterations over (74,3) keys ~= 5e4 flop-equivalents
    mf = 5e4 * nv / n_dev
    roof = RL.analyze(compiled, mf)
    print("roofline:", roof.summary())
    return {
        "arch": f"ddms:{field}", "shape": f"{dims[0]}x{dims[1]}x{dims[2]}",
        "mesh": "multi" if multi_pod else "single", "n_devices": n_dev,
        "lower_s": t_lower, "compile_s": t_compile,
        "flops_per_device": roof.flops,
        "bytes_per_device": roof.bytes_accessed,
        "collectives": roof.coll,
        "compute_s": roof.compute_s, "memory_s": roof.memory_s,
        "collective_s": roof.collective_s, "dominant": roof.dominant,
        "model_flops_per_device": mf, "useful_ratio": roof.useful_ratio,
        "memory_analysis": mem,
        "config": {"crit_cap": crit_cap, "ring_rotations": ring_rotations,
                   "gradient_chunk": gradient_chunk,
                   "use_sample_sort": use_sample_sort},
    }


def run_cell(arch, shape_name, mesh_kind, out_dir: Path, skip_existing=True,
             tag="", **kw):
    out = out_dir / f"{arch.replace(':','_')}__{shape_name}__{mesh_kind}" \
        f"{('__' + tag) if tag else ''}.json"
    if skip_existing and out.exists():
        print("exists:", out.name)
        return
    print(f"=== {arch} x {shape_name} x {mesh_kind} ===", flush=True)
    try:
        if arch.startswith("ddms:"):
            rec = lower_ddms(arch.split(":", 1)[1],
                             multi_pod=(mesh_kind == "multi"), **kw)
        else:
            # exact-cost extrapolation only for the single-pod mesh (the
            # roofline table is single-pod; multi-pod proves the pod axis)
            kw.setdefault("exact", mesh_kind == "single")
            rec = lower_cell(arch, shape_name,
                             multi_pod=(mesh_kind == "multi"), **kw)
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        print("FAILED:", rec["error"], flush=True)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1, default=str))
    print("wrote", out.name, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--ddms", default=None, choices=list(DDMS_FIELDS))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        for mk in meshes:
            for arch in ARCHS:
                for shape in SHAPES:
                    run_cell(arch, shape, mk, out_dir,
                             skip_existing=args.skip_existing)
            for fld in DDMS_FIELDS:
                run_cell(f"ddms:{fld}", "field", mk, out_dir,
                         skip_existing=args.skip_existing)
        return
    if args.ddms:
        for mk in meshes:
            run_cell(f"ddms:{args.ddms}", "field", mk, out_dir,
                     skip_existing=args.skip_existing)
        return
    assert args.arch and args.shape
    for mk in meshes:
        run_cell(args.arch, args.shape, mk, out_dir,
                 skip_existing=args.skip_existing)


if __name__ == "__main__":
    main()
