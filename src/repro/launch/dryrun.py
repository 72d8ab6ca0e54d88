import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any other import (jax locks device count on first init).

_DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x
mesh) cell, prove the sharding is coherent, and extract the roofline terms.

For each cell this produces a JSON record under experiments/dryrun/:
  memory_analysis   - bytes per device (proves it fits / flags overage)
  cost_analysis     - HLO FLOPs + bytes accessed
  collectives       - per-op-kind counts + bytes parsed from optimized HLO
  roofline          - compute / memory / collective terms (launch.roofline)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
      --shape train_4k [--multi-pod] [--plan overrides.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import roofline
from repro.launch.mesh import dp_axes, make_production_mesh, tp_size
from repro.models import lm
from repro.models.lm import SHAPES, ShapeSpec
from repro.parallel.sharding import (param_pspecs, param_shardings,
                                     resolve_spec)
from repro.train.optimizer import cosine_schedule
from repro.train.train_step import init_train_state, make_train_step
from repro.utils.hlo import collectives_with_trips
from repro.utils.jaxpr_cost import lowered_cost


@dataclasses.dataclass
class RunPlan:
    """Per-cell performance knobs (the hillclimb surface)."""
    accum: int = 8                 # gradient-accumulation microbatches
    remat: bool = True
    kv_chunk: int = 1024
    xent_chunk: int = 2048
    opt_dtype: str = "float32"     # bf16 for the 671B MoE
    cache_dtype: str = "bfloat16"
    donate: bool = True
    moe_impl: str = "auto"         # 'dense' baseline | 'auto'/'ep' shard_map
    sharding: str = "tp"           # 'tp' | 'fsdp' | 'dp' parameter ruleset
    grad_dtype: str = "float32"    # bf16 halves grad-AR wire volume
    md_impl: str = "stencil"       # 'stencil' baseline | 'pruned' prestaged


# arch/shape-specific overrides (memory fits derived in EXPERIMENTS.md)
PLAN_OVERRIDES: dict[tuple[str, str], dict] = {
    ("deepseek-v3-671b", "train_4k"): dict(accum=8, opt_dtype="bfloat16"),
    ("pixtral-12b", "train_4k"): dict(accum=8),
    ("qwen2-7b", "prefill_32k"): dict(kv_chunk=2048),
}


def plan_for(arch: str, shape: str, overrides: dict | None = None) -> RunPlan:
    plan = RunPlan()
    for k, v in PLAN_OVERRIDES.get((arch, shape), {}).items():
        setattr(plan, k, v)
    for k, v in (overrides or {}).items():
        setattr(plan, k, v)
    return plan


def _sds(tree, shardings):
    """Attach shardings to a ShapeDtypeStruct pytree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _batch_shardings(mesh, batch_abs):
    dp = dp_axes(mesh)
    def f(x):
        spec = [dp if x.shape[0] % np.prod([mesh.shape[a] for a in dp]) == 0
                else None] + [None] * (x.ndim - 1)
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(f, batch_abs)


def _replicated(mesh, tree):
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def lower_lm_cell(arch: str, shape_name: str, mesh, plan: RunPlan):
    """Returns (lowered, compiled, meta) for one LM cell."""
    cfg = configs.get(arch)
    if cfg.moe is not None and plan.moe_impl != cfg.moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=plan.moe_impl)
    shape = SHAPES[shape_name]
    ok, reason = lm.shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": reason}
    tp = tp_size(mesh)

    params_abs = lm.abstract_params(cfg, tp=tp)
    pshard = param_shardings(mesh, params_abs, plan.sharding)
    batch_abs = lm.input_specs(cfg, shape)
    bshard = _batch_shardings(mesh, batch_abs)
    batch_in = _sds(batch_abs, bshard)

    if shape.kind == "train":
        opt_dtype = jnp.dtype(plan.opt_dtype)
        state_abs = jax.eval_shape(
            lambda p: init_train_state(p, opt_dtype), params_abs)
        from repro.parallel.sharding import opt_shardings
        sshard = jax.tree_util.tree_map(lambda x: None, state_abs)
        sshard = type(state_abs)(
            params=pshard,
            opt=type(state_abs.opt)(
                mu=opt_shardings(mesh, params_abs),
                nu=opt_shardings(mesh, params_abs),
                count=NamedSharding(mesh, P())),
            step=NamedSharding(mesh, P()))
        state_in = _sds(state_abs, sshard)

        loss_fn = lm.make_loss_fn(cfg, remat=plan.remat,
                                  kv_chunk=plan.kv_chunk,
                                  xent_chunk=plan.xent_chunk)
        from repro.parallel.sharding import set_mode
        set_mode(plan.sharding)
        step_fn = make_train_step(
            loss_fn, lambda s: cosine_schedule(s, peak_lr=3e-4, warmup=100,
                                               total=10000),
            accum=plan.accum, grad_dtype=jnp.dtype(plan.grad_dtype))
        jitted = jax.jit(step_fn,
                         donate_argnums=(0,) if plan.donate else ())
        with jax.set_mesh(mesh):
            traced = jitted.trace(state_in, batch_in)
            lowered = traced.lower()
            compiled = lowered.compile()
        tokens = shape.global_batch * shape.seq_len
        return lowered, compiled, {"kind": "train", "tokens": tokens,
                                   "jaxpr_cost": lowered_cost(traced.jaxpr)}

    if shape.kind == "prefill":
        fn = lm.make_prefill_fn(cfg, kv_chunk=plan.kv_chunk)
        jitted = jax.jit(fn)
        with jax.set_mesh(mesh):
            traced = jitted.trace(_sds(params_abs, pshard), batch_in)
            lowered = traced.lower()
            compiled = lowered.compile()
        tokens = shape.global_batch * shape.seq_len
        return lowered, compiled, {"kind": "prefill", "tokens": tokens,
                                   "jaxpr_cost": lowered_cost(traced.jaxpr)}

    # decode
    cache_abs = lm.cache_specs(cfg, shape, jnp.dtype(plan.cache_dtype))
    cshard = _cache_shardings(mesh, cache_abs)
    fn = lm.make_decode_fn(cfg)
    jitted = jax.jit(fn, donate_argnums=(1,) if plan.donate else ())
    with jax.set_mesh(mesh):
        traced = jitted.trace(_sds(params_abs, pshard),
                              _sds(cache_abs, cshard), batch_in)
        lowered = traced.lower()
        compiled = lowered.compile()
    return lowered, compiled, {"kind": "decode",
                               "tokens": shape.global_batch,
                               "jaxpr_cost": lowered_cost(traced.jaxpr)}


def _cache_shardings(mesh, cache_abs):
    """Caches: batch dim over DP axes; head dim over model when divisible.
    Cache leaves are (L, B, T, H, hd) or (L, B, ...)."""
    dp = dp_axes(mesh)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))
    tp = mesh.shape.get("model", 1)

    def f(x):
        spec = [None] * x.ndim
        if x.ndim >= 2 and x.shape[1] % dpn == 0 and x.shape[1] >= dpn:
            spec[1] = dp
        # shard a heads-like dim over model: prefer dim 3 (L,B,T,H,...)
        for d in (3, 4):
            if x.ndim > d and x.shape[d] % tp == 0 and x.shape[d] >= tp:
                spec[d] = "model"
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(f, cache_abs)


# ---------------------------------------------------------------------------
# MD (the paper's workload)
# ---------------------------------------------------------------------------

MD_SHAPES = {
    # per-device cell grids: analogue of the paper's weak-scaling cases
    "md_small": (8, 8, 8),      # ~0.13M atoms/device, 67M @ 512 chips
    "md_large": (16, 16, 16),   # ~1.05M atoms/device, 536M @ 512 chips
}


def lower_md_cell(shape_name: str, mesh, plan: RunPlan):
    from repro.launch.md_step import build_md_dryrun
    return build_md_dryrun(shape_name, mesh, dtype=jnp.float32,
                           impl=plan.md_impl)


# ---------------------------------------------------------------------------
# analysis + records
# ---------------------------------------------------------------------------

def analyze(lowered, compiled, meta, arch, shape_name, mesh) -> dict:
    n_dev = mesh.size
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may lack it
        mem_rec = {"error": str(e)}
    hlo = compiled.as_text()
    coll_rec = collectives_with_trips(hlo)
    coll = coll_rec["per_kind"]
    jc = meta.pop("jaxpr_cost", None)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "devices": n_dev,
        "meta": meta,
        # per-device: jaxpr count is global -> divide by devices (SPMD)
        "flops_total": (jc["flops"] / n_dev) if jc else
        float(cost.get("flops", 0.0)),
        "flops_xla_body": float(cost.get("flops", 0.0)),
        # anchor bytes: dot/gather/scatter-class HBM traffic (fusion-aware);
        # naive = every op's in+out (upper bound)
        "bytes_total": (jc["bytes_anchor"] / n_dev) if jc else
        float(cost.get("bytes accessed", 0.0)),
        "bytes_naive": (jc["bytes_naive"] / n_dev) if jc else None,
        "bytes_xla_body": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "collective_trips_unknown": coll_rec.get("unknown_trips", False),
        "memory": mem_rec,
    }
    rec["roofline"] = roofline.terms(rec)
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "experiments/dryrun",
             overrides: dict | None = None, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = plan_for(arch, shape_name, overrides)
    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    t0 = time.time()
    try:
        if arch == "fege-spinlattice":
            lowered, compiled, meta = lower_md_cell(shape_name, mesh, plan)
        else:
            lowered, compiled, meta = lower_lm_cell(arch, shape_name, mesh,
                                                    plan)
        if lowered is None:
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": dict(mesh.shape), "skipped": meta["skipped"]}
        else:
            rec = analyze(lowered, compiled, meta, arch, shape_name, mesh)
            rec["plan"] = dataclasses.asdict(plan)
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    rec["elapsed_s"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    if verbose:
        if "error" in rec:
            print(f"FAIL {tag}: {rec['error']}")
        elif "skipped" in rec:
            print(f"SKIP {tag}: {rec['skipped']}")
        else:
            r = rec["roofline"]
            print(f"OK   {tag}  flops={rec['flops_total']:.3e} "
                  f"coll={r['collective_bytes']:.3e}B "
                  f"bound={r['bottleneck']} ({rec['elapsed_s']}s)")
    return rec


def main():
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--plan", default=None, help="JSON plan overrides")
    args = ap.parse_args()

    overrides = json.loads(args.plan) if args.plan else None
    if args.all:
        cells = [(a, s) for a in configs.ARCHS for s in SHAPES]
        cells += [("fege-spinlattice", s) for s in MD_SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]
    for arch, shape in cells:
        run_cell(arch, shape, args.multi_pod, args.out, overrides)


if __name__ == "__main__":
    main()
