import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first backend initialization (see MULTI-POD DRY-RUN contract).

"""Multi-pod dry-run: AOT lower+compile every (arch × shape × mesh) cell.

For each cell we build the real pjit-ed step (train_step / forward /
decode_step) with production shardings, lower it against ShapeDtypeStructs
(params, optimizer state, batch, caches — nothing is ever allocated),
compile, and extract:
  - memory_analysis()  -> per-device HBM footprint (proves it fits)
  - cost_analysis()    -> per-device FLOPs / bytes accessed
  - compiled HLO text  -> per-collective byte counts (roofline term 3)
Artifacts are cached as JSON under artifacts/dryrun/.
"""
import argparse
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import SHAPES, ModelConfig, RunConfig, ShapeConfig, supported_shapes
from ..configs import ARCHS, get_config
from ..distributed.sharding import (cache_pspecs, input_pspecs, logits_pspec,
                                    param_pspecs)
from ..models.model import decode_step, forward, input_specs, param_shapes
from ..optim import opt_state_shapes
from ..roofline import Roofline, collective_bytes, model_flops_for
from ..train.step import train_step
from .mesh import make_production_mesh

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _ns(mesh: Mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, tree,
        is_leaf=lambda x: isinstance(x, P))


def resolved_operating_point(shape: ShapeConfig):
    """The cell's machine-model operating point — cluster geometry included
    — from the calibration-backed :class:`~repro.core.policy.PolicyTable`
    (``REPRO_CALIBRATION_DIR`` honoured): training shapes resolve the
    ``train`` workload, prefill/decode the ``serve`` one.  The dry-run cost
    model no longer implicitly assumes one PE; the resolved point is
    embedded in every cell artifact (``machine_model`` block)."""
    from ..core.policy import default_table
    workload = "train" if shape.mode == "train" else "serve"
    return default_table().resolve(workload)


def default_runconfig(shape: ShapeConfig, policy: Optional[str] = None,
                      analysis: bool = False) -> RunConfig:
    from ..core.policy import ExecutionPolicy
    if policy is None:        # calibrated table point; explicit string wins
        policy = resolved_operating_point(shape).policy.value
    return RunConfig(policy=ExecutionPolicy.parse(policy),
                     dtype="bfloat16",
                     param_dtype="float32" if shape.mode == "train" else "bfloat16",
                     remat=(shape.mode == "train"),
                     fsdp=True,    # ZeRO-style weight sharding over 'data'
                     #   in inference too: a 341B model's bf16 weights are
                     #   43 GB/chip under TP=16 alone (EXPERIMENTS §Dry-run)
                     moe_dispatch="grouped",       # deployable dispatch path
                     attn_batch_shard=True,        # see EXPERIMENTS.md §Perf
                     analysis_mode=analysis)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               rc: Optional[RunConfig] = None):
    """Build + lower the pjit step for one cell (traced inside a mesh
    context so PartitionSpec sharding constraints resolve)."""
    rc = rc or default_runconfig(shape)
    with jax.set_mesh(mesh):
        return _lower_cell_inner(cfg, shape, mesh, rc)


def _lower_cell_inner(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      rc: RunConfig):
    pdt = jnp.dtype(rc.param_dtype)
    pspec = param_pspecs(cfg, mesh, rc)
    pshapes = param_shapes(cfg, pdt)
    batch_specs = input_specs(cfg, shape, rc)
    batch_pspecs = input_pspecs(cfg, shape, mesh)

    if shape.mode == "train":
        from ..optim import OptState
        ospec = OptState(step=P(), mu=pspec, nu=pspec)
        oshapes = opt_state_shapes(pshapes)
        fn = jax.jit(partial(train_step, cfg=cfg, rc=rc),
                     in_shardings=(_ns(mesh, pspec), _ns(mesh, ospec),
                                   _ns(mesh, batch_pspecs)),
                     out_shardings=(_ns(mesh, pspec), _ns(mesh, ospec), None),
                     donate_argnums=(0, 1))
        return fn.lower(pshapes, oshapes, batch_specs)

    if shape.mode == "prefill":
        fn = jax.jit(partial(forward, cfg=cfg, rc=rc),
                     in_shardings=(_ns(mesh, pspec), _ns(mesh, batch_pspecs)),
                     out_shardings=_ns(mesh, logits_pspec(cfg, shape, mesh)))
        return fn.lower(pshapes, batch_specs)

    # decode
    cache_shapes = batch_specs["cache"]
    cpspec = cache_pspecs(cfg, shape, mesh)
    fn = jax.jit(partial(decode_step, cfg=cfg, rc=rc),
                 in_shardings=(_ns(mesh, pspec), _ns(mesh, cpspec),
                               _ns(mesh, {"tokens": P(None, None)})),
                 out_shardings=(_ns(mesh, logits_pspec(cfg, shape, mesh)),
                                _ns(mesh, cpspec)),
                 donate_argnums=(1,))
    return fn.lower(pshapes, cache_shapes,
                    {"tokens": batch_specs["tokens"]})


def _measure(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
             rc: RunConfig) -> Dict[str, Any]:
    """Lower + compile one configuration and extract cost metrics."""
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, rc)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        }
    except Exception:                                    # backend-dependent
        mem_info = {"argument_bytes": None, "output_bytes": None,
                    "temp_bytes": None, "peak_bytes": None}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "memory": mem_info,
        "lower_s": t_lower, "compile_s": t_compile,
    }


def _with_layers(cfg: ModelConfig, units: int) -> ModelConfig:
    """A config with ``units`` repeating units (layers, or hybrid macros) —
    the tail of a hybrid config is kept verbatim."""
    import dataclasses
    if cfg.family == "hybrid":
        pat = len(cfg.rglru.pattern)
        tail = cfg.n_layers % pat
        return dataclasses.replace(cfg, n_layers=pat * units + tail)
    return dataclasses.replace(cfg, n_layers=units)


def _n_units(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // len(cfg.rglru.pattern)
    return cfg.n_layers


def analytic_device_bytes(cfg: ModelConfig, shape: ShapeConfig,
                          mesh: Mesh, rc: RunConfig) -> Dict[str, float]:
    """Exact per-device bytes of the *persistent* state (params, optimizer,
    decode caches) from the actual leaf shardings — the trustworthy HBM
    check (XLA:CPU memory_analysis reports logical buffer bytes)."""
    import numpy as np
    from ..models.model import cache_spec

    pdt = jnp.dtype(rc.param_dtype).itemsize
    pspec = param_pspecs(cfg, mesh, rc)
    shapes = param_shapes(cfg, jnp.dtype(rc.param_dtype))
    leaves = jax.tree_util.tree_leaves(shapes)
    specs = jax.tree_util.tree_leaves(
        pspec, is_leaf=lambda x: isinstance(x, P))

    def per_dev(shape_, spec):
        n = int(np.prod(shape_)) if shape_ else 1
        div = 1
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                div *= mesh.shape[a]
        return n / div

    params = sum(per_dev(l.shape, s) * pdt for l, s in zip(leaves, specs))
    out = {"params_gb": params / 1e9}
    if shape.mode == "train":
        out["opt_gb"] = 2 * sum(per_dev(l.shape, s) * 4
                                for l, s in zip(leaves, specs)) / 1e9
    if shape.mode == "decode":
        cdt = jnp.dtype(rc.dtype).itemsize
        cspec = cache_pspecs(cfg, shape, mesh)
        cshape = cache_spec(cfg, shape.global_batch, shape.seq_len,
                            jnp.dtype(rc.dtype))
        cl = jax.tree_util.tree_leaves(cshape)
        cs = jax.tree_util.tree_leaves(cspec,
                                       is_leaf=lambda x: isinstance(x, P))
        out["cache_gb"] = sum(per_dev(l.shape, s) * l.dtype.itemsize
                              for l, s in zip(cl, cs)) / 1e9
    out["total_gb"] = sum(v for k, v in out.items() if k.endswith("_gb"))
    return out


def cell_tag(arch: str, shape_name: str, multi_pod: bool,
             policy: Optional[str], analysis: bool) -> str:
    """The one source of truth for a cell's artifact tag (and hence its
    cache filename): ``policy=None`` resolves the workload's calibrated
    operating point exactly like :func:`run_cell` does."""
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    variant = "analysis" if analysis else "deploy"
    if policy is None:
        policy = resolved_operating_point(SHAPES[shape_name]).policy.value
    return f"{arch}_{shape_name}_{mesh_name}_{policy}_{variant}"


def cell_path(arch: str, shape_name: str, multi_pod: bool,
              policy: Optional[str], analysis: bool) -> str:
    return os.path.join(
        ART_DIR, f"{cell_tag(arch, shape_name, multi_pod, policy, analysis)}"
        ".json")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy: Optional[str] = None, rc: Optional[RunConfig] = None,
             save: bool = True, analysis: bool = False) -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    variant = "analysis" if analysis else "deploy"
    op = resolved_operating_point(SHAPES[shape_name])
    if policy is None:
        policy = op.policy.value
    tag = cell_tag(arch, shape_name, multi_pod, policy, analysis)
    path = os.path.join(ART_DIR, f"{tag}.json")
    if save and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rc = rc or default_runconfig(shape, policy, analysis=analysis)

    if analysis:
        # Two-point extrapolation: XLA's cost analysis counts loop bodies
        # once, so we lower FULLY UNROLLED models with 1 and 2 repeating
        # units; per-unit costs are their difference (layers are uniform),
        # totals are exact: A(L) = A(1) + (L-1)·(A(2)-A(1)).
        m1 = _measure(_with_layers(cfg, 1), shape, mesh, rc)
        m2 = _measure(_with_layers(cfg, 2), shape, mesh, rc)
        L = _n_units(cfg)
        flops = m1["flops"] + (L - 1) * (m2["flops"] - m1["flops"])
        bytes_accessed = m1["bytes"] + (L - 1) * (m2["bytes"] - m1["bytes"])
        coll = {}
        keys = set(m1["coll"]) | set(m2["coll"])
        for k in keys:
            a, b = m1["coll"].get(k, 0), m2["coll"].get(k, 0)
            coll[k] = int(a + (L - 1) * (b - a))
        mem_info = m1["memory"]                  # footprint: see deploy cell
        cost = {"flops": flops, "bytes accessed": bytes_accessed,
                "extrapolated_from_units": [1, 2]}
        t_lower = m1["lower_s"] + m2["lower_s"]
        t_compile = m1["compile_s"] + m2["compile_s"]
    else:
        m = _measure(cfg, shape, mesh, rc)
        flops, bytes_accessed, coll = m["flops"], m["bytes"], m["coll"]
        mem_info, cost = m["memory"], m["cost"]
        t_lower, t_compile = m["lower_s"], m["compile_s"]

    rl = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        per_device_flops=flops, per_device_bytes=bytes_accessed,
        per_device_coll_bytes=float(coll.get("total", 0)),
        model_flops=model_flops_for(cfg, shape),
        per_device_hbm_peak=mem_info["peak_bytes"])
    analytic = analytic_device_bytes(cfg, shape, mesh, rc)
    art = {
        "tag": tag, "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "policy": policy, "chips": chips, "variant": variant,
        "analytic_device_gb": analytic,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {k: (float(v) if isinstance(v, (int, float)) else v)
                          for k, v in cost.items()},
        "memory": mem_info,
        "collectives": coll,
        "roofline": rl.to_dict(),
        # the machine-model operating point the cost model assumes: the
        # calibrated (or default) cluster-level point for this workload —
        # per-PE queue geometry plus how many PEs share the TCDM.  An
        # explicit --policy / caller rc pin overrides the table's policy;
        # the block reports the policy the cell actually ran under.
        "machine_model": {
            "workload": "train" if shape.mode == "train" else "serve",
            "source": (op.source if rc.policy is op.policy else "override"),
            "policy": rc.policy.value,
            "queue_depth": op.queue_depth,
            "queue_depth_i2f": op.queue_depth_i2f,
            "queue_depth_f2i": op.queue_depth_f2i,
            "unroll": op.unroll,
            "n_cores": op.n_cores,
            "tcdm_banks": op.tcdm_banks,
        },
        "ok": True,
    }
    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(art, f, indent=1)
    return art


def all_cells(multi_pod_also: bool = True, analysis_also: bool = True):
    """(arch, shape, multi_pod, analysis) triples: the deployable lowering on
    both meshes (compile gate + memory) and the unrolled analysis lowering on
    the single pod (roofline terms)."""
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape_name in supported_shapes(cfg):
            yield arch, shape_name, False, False
            if analysis_also:
                yield arch, shape_name, False, True
            if multi_pod_also:
                yield arch, shape_name, True, False


def main() -> None:
    ap = argparse.ArgumentParser(description="Multi-pod AOT dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--policy", default=None,
                    help="pin the execution policy (default: resolve the "
                         "workload's calibrated operating point)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fresh", action="store_true", help="ignore cache")
    ap.add_argument("--analysis", action="store_true",
                    help="unrolled analysis lowering (true roofline totals)")
    ap.add_argument("--no-analysis", action="store_true",
                    help="with --all: skip analysis variants")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = list(all_cells(
            multi_pod_also=(args.mesh in ("multipod", "both")),
            analysis_also=not args.no_analysis))
        if args.mesh == "multipod":
            cells = [c for c in cells if c[2]]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        meshes = {"pod": [False], "multipod": [True], "both": [False, True]}
        cells = [(args.arch, args.shape, mp, args.analysis)
                 for mp in meshes[args.mesh]]

    failures = []
    for arch, shape_name, mp, analysis in cells:
        var = "analysis" if analysis else "deploy"
        tag = f"{arch}/{shape_name}/{'2x16x16' if mp else '16x16'}/{var}"
        path = cell_path(arch, shape_name, mp, args.policy, analysis)
        if args.fresh and os.path.exists(path):
            os.remove(path)
        try:
            art = run_cell(arch, shape_name, mp, policy=args.policy,
                           analysis=analysis)
            rl = art["roofline"]
            print(f"OK  {tag:<58} compile={art['compile_s']:>7.1f}s "
                  f"bottleneck={rl['bottleneck']:<10} "
                  f"t=({rl['t_compute']:.2e},{rl['t_memory']:.2e},"
                  f"{rl['t_collective']:.2e})s mfu={rl['mfu']:.3f}",
                  flush=True)
        except Exception as e:
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed")


if __name__ == "__main__":
    main()
