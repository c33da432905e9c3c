"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST keep the next two statements first — jax locks the device count at
first initialization, and only the dry-run may see 512 placeholder devices.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (CI smoke override — still before any jax import:)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

import argparse     # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro.configs as C                      # noqa: E402
from repro.analysis import hlo_cost            # noqa: E402
from repro.core import roofline as rl          # noqa: E402
from repro.launch.mesh import make_production_mesh, make_mesh  # noqa: E402
from repro.models import transformer           # noqa: E402
from repro.models.params import (tree_abstract, tree_shardings)  # noqa: E402
from repro.serve import serve_step as serve    # noqa: E402
from repro.train import optimizer as opt       # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402

HW = rl.TPU_V5E


# --------------------------------------------------------------- programs --
def lower_cell(cfg, shape_name: str, mesh, attn_impl: str | None = None,
               sharding: str | None = None, ssm_impl: str | None = None):
    """Lower + compile one (arch × shape) cell on ``mesh``; returns
    (lowered, compiled, meta)."""
    import dataclasses
    if sharding:
        cfg = dataclasses.replace(cfg, sharding=sharding)
    cfgm = cfg.with_mesh(mesh)
    if attn_impl:
        cfgm = dataclasses.replace(cfgm, attention_impl=attn_impl)
    if ssm_impl:
        cfgm = dataclasses.replace(cfgm, ssm_impl=ssm_impl)
    info = C.SHAPES[shape_name]
    kind, b, s = info["kind"], info["batch"], info["seq"]
    pdefs = transformer.param_defs(cfgm)
    p_abs = tree_abstract(pdefs, cfgm.param_dtype)
    p_sh = tree_shardings(pdefs, mesh)
    batch_abs = cfgm.input_specs(shape_name)
    batch_sh = {k: NamedSharding(mesh, v)
                for k, v in cfgm.input_pspecs(shape_name).items()}

    if kind == "train":
        ocfg = opt.OptConfig(schedule=cfgm.schedule)
        odefs = opt.opt_state_defs(pdefs, data_size=cfgm.mesh_dp)
        o_abs = tree_abstract(odefs)
        o_sh = tree_shardings(odefs, mesh)
        fn = make_train_step(cfgm, ocfg)
        jfn = jax.jit(fn, in_shardings=(p_sh, o_sh, batch_sh),
                      out_shardings=(p_sh, o_sh, None),
                      donate_argnums=(0, 1))
        with mesh:
            lowered = jfn.lower(p_abs, o_abs, batch_abs)
    elif kind == "prefill":
        fn = serve.make_prefill(cfgm, cache_len=s)
        jfn = jax.jit(fn, in_shardings=(p_sh, batch_sh))
        with mesh:
            lowered = jfn.lower(p_abs, batch_abs)
    else:  # decode
        cdefs = transformer.cache_defs(cfgm, b, s)
        c_abs = tree_abstract(cdefs, cfgm.activ_dtype)
        c_sh = tree_shardings(cdefs, mesh)
        fn = serve.make_decode_step(cfgm)
        tok_sh = NamedSharding(
            mesh, P(cfgm.dp_axes if b % max(1, cfgm.mesh_dp) == 0
                    and b >= cfgm.mesh_dp > 1 else None, None))
        jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh, None),
                      donate_argnums=(1,))
        with mesh:
            lowered = jfn.lower(p_abs, c_abs,
                                jax.ShapeDtypeStruct((b, 1), jnp.int32),
                                jax.ShapeDtypeStruct((), jnp.int32))
    t0 = time.time()
    compiled = lowered.compile()
    return lowered, compiled, {"compile_s": time.time() - t0, "kind": kind,
                               "tokens": b * s if kind != "decode" else b,
                               "cfg": cfgm}


def model_flops(cfg, shape_name: str) -> float:
    """Analytic 6·N·D (train) / 2·N·D (inference) FLOPs, N = active params."""
    info = C.SHAPES[shape_name]
    n = cfg.n_active_params()
    tokens = (info["batch"] * info["seq"]
              if info["kind"] != "decode" else info["batch"])
    return (6.0 if info["kind"] == "train" else 2.0) * n * tokens


def roofline_terms(cost: hlo_cost.HloCost, n_chips: int, mesh_axes):
    """Per-chip three-term roofline (numerators are per-device = global/chips
    for SPMD programs)."""
    t_comp = cost.dot_flops / HW.mxu_flops
    t_mem = cost.bytes_accessed / HW.b_gm
    links = HW.b_ici * max(1, HW.ici_links // 2)
    t_coll = cost.total_wire_bytes / links
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    return terms, dom


# ---------------------------------------------------------------- stencil --
def run_stencil_cell(spec_name: str, mesh, t_block: int | None = None,
                     inner: str = "jnp"):
    from repro.core.distributed import make_distributed_stencil
    from repro.core.planner import plan
    from repro.core.stencil_spec import get
    spec = get(spec_name)
    pl = plan(spec, HW)
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp = dp if len(dp) > 1 else dp[0]
    dp_size = math.prod(v for k, v in axes.items() if k in ("pod", "data"))
    mdl = axes.get("model", 1)
    dim_to_axis = {0: dp, 1: "model"} if spec.ndim == 2 else \
        {0: dp, 1: "model"}
    # round the domain up so every sharded dim divides its axis
    dom = list(spec.domain)
    dom[0] = math.ceil(dom[0] / dp_size) * dp_size
    dom[1] = math.ceil(dom[1] / mdl) * mdl
    tb = t_block or max(1, min(pl.t, dom[0] // dp_size // spec.radius,
                               dom[1] // mdl // spec.radius))
    t_total = int(os.environ.get("REPRO_STENCIL_TTOTAL", 0)) or tb * 2
    assert t_total % tb == 0
    fn, pspec = make_distributed_stencil(spec, mesh, dim_to_axis,
                                         tuple(dom), t_total, tb,
                                         inner=inner)
    x_abs = jax.ShapeDtypeStruct(tuple(dom), jnp.float32)
    with mesh:
        lowered = fn.lower(x_abs)
    t0 = time.time()
    compiled = lowered.compile()
    meta = {"compile_s": time.time() - t0, "kind": "stencil",
            "tokens": math.prod(dom) * t_total, "t_block": tb,
            "t_total": t_total, "domain": dom}
    return lowered, compiled, meta


# ------------------------------------------------------------------- main --
def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, outdir: str,
             attn_impl: str | None = None, sharding: str | None = None,
             ssm_impl: str | None = None):
    n_chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_chips": int(n_chips)}
    if attn_impl:
        rec["mesh"] = mesh_name = f"{mesh_name}-{attn_impl}"
    if sharding:
        rec["mesh"] = mesh_name = f"{mesh_name}-{sharding}"
    if ssm_impl:
        rec["mesh"] = mesh_name = f"{mesh_name}-ssmstub"
    try:
        if arch == "stencil-suite":
            lowered, compiled, meta = run_stencil_cell(
                shape_name, mesh,
                t_block=int(os.environ.get("REPRO_STENCIL_TBLOCK", 0)) or None,
                inner=os.environ.get("REPRO_STENCIL_INNER", "jnp"))
            rec["t_block"] = meta["t_block"]
            from repro.core.stencil_spec import get
            spec = get(shape_name)
            rec["model_flops"] = (spec.flops_per_cell * meta["tokens"])
        else:
            cfg = C.get_config(arch)
            ok, why = cfg.supports(shape_name)
            if not ok:
                rec.update(status="skipped", reason=why)
                _write(outdir, rec)
                return rec
            lowered, compiled, meta = lower_cell(cfg, shape_name, mesh,
                                                 attn_impl, sharding,
                                                 ssm_impl)
            rec["model_flops"] = model_flops(cfg, shape_name)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        cost = hlo_cost.analyze(compiled.as_text())
        terms, dom = roofline_terms(cost, n_chips, mesh.axis_names)
        mf_chip = rec["model_flops"] / n_chips
        peak = HW.mxu_flops
        if arch == "stencil-suite":
            # stencils run on the VPU (elementwise FMA, no dots): both the
            # compute term and the roofline use the VPU peak
            peak = HW.thr_cmp
            terms["compute_s"] = mf_chip / peak
        dom = max(terms, key=terms.get)
        step_time = max(terms.values())
        rec.update(
            status="ok",
            compile_s=round(meta["compile_s"], 2),
            memory=dict(
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                code_bytes=int(ma.generated_code_size_in_bytes),
                peak_per_device=int(ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
            ),
            cost_analysis_raw=dict(
                flops=float(ca.get("flops", -1)),
                bytes_accessed=float(ca.get("bytes accessed", -1)),
            ),
            hlo=cost.as_dict(),
            terms=terms,
            dominant=dom,
            roofline_fraction=(mf_chip / peak) / step_time
            if step_time > 0 else None,
            useful_flops_ratio=(mf_chip / cost.dot_flops
                                if cost.dot_flops else None),
            hbm_ok=bool(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        - ma.alias_size_in_bytes < HW.hbm_bytes),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    _write(outdir, rec)
    return rec


def _write(outdir, rec):
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir,
                        f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    t = rec.get("terms", {})
    print(f"[{rec['status']:7s}] {rec['arch']:24s} {rec['shape']:12s} "
          f"{rec['mesh']:6s} compile={rec.get('compile_s', '-')}s "
          f"dom={rec.get('dominant', '-')} "
          f"roofline={rec.get('roofline_fraction') and round(rec['roofline_fraction'], 3)} "
          f"{rec.get('reason', '') or rec.get('error', '')[:120] if rec['status']=='error' else rec.get('reason','')}",
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both", "smoke"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--attn", default=None,
                    choices=[None, "flash_jnp", "boundary_stub"])
    ap.add_argument("--sharding", default=None, choices=[None, "tp", "fsdp"])
    ap.add_argument("--ssm", default=None,
                    choices=[None, "chunked_jnp", "boundary_stub"])
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))
    if args.mesh == "smoke":
        n = jax.device_count()
        meshes.append(("smoke", make_mesh((max(1, n // 4), 4),
                                          ("data", "model"))))

    archs = (C.list_archs() if args.arch == "all" else args.arch.split(","))
    for mesh_name, mesh in meshes:
        for arch in archs:
            if arch == "stencil-suite":
                from repro.core.stencil_spec import names
                shapes = names() if args.shape == "all" \
                    else args.shape.split(",")
            else:
                shapes = (list(C.SHAPES) if args.shape == "all"
                          else args.shape.split(","))
            for shape in shapes:
                run_cell(arch, shape, mesh, mesh_name, args.out, args.attn,
                         args.sharding, args.ssm)


if __name__ == "__main__":
    main()
