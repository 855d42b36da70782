import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import (jax locks the device count on first init).

r"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, WITHOUT allocating a single model buffer:
  * proof the sharding config is coherent (``.lower().compile()`` succeeds),
  * ``compiled.memory_analysis()``  — bytes/device (fits-in-HBM evidence),
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline,
  * collective bytes parsed from the optimized HLO (per collective kind),
all dumped as one JSON artifact per cell under ``benchmarks/artifacts/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--cma]

Exit code 0 iff every requested cell compiled.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES, cells_for
from repro.distributed import sharding
from repro.distributed.hlo_analyzer import analyze
from repro.launch import mesh as mesh_mod
from repro.launch import specs as specs_mod
from repro.serve import engine as serve_engine
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../benchmarks/artifacts")

# grad-accumulation depth per arch for train_4k (fits-in-HBM tuning; the
# dry-run memory analysis below is the evidence)
MICROBATCHES = {
    "gemma3-27b": 8,
    "llama-3.2-vision-90b": 16,
    "phi3.5-moe-42b-a6.6b": 8,
    "zamba2-7b": 4,
    "moonshot-v1-16b-a3b": 4,
    "phi3-mini-3.8b": 2,
    "gemma3-4b": 2,
    "rwkv6-3b": 2,
    "musicgen-large": 2,
}


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))


def _batch_shardings(mesh, batch_abstract):
    dp = sharding.dp_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]

    def spec(x):
        lead = dp if (x.shape and x.shape[0] % dp_size == 0) else None
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(lead,
                                             *([None] * (len(x.shape) - 1))))
    return jax.tree_util.tree_map(spec, batch_abstract)


def lower_cell(arch: str, shape_name: str, mesh, *, microbatches=None,
               overrides: dict | None = None):
    """Returns (lowered, compiled, meta) for one cell.

    ``overrides`` — ModelConfig field overrides for §Perf experiments
    (e.g. {"attn_impl": "flash"}); recorded in the artifact.
    """
    import dataclasses
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    # TrainConfig-level knobs routed out of the ModelConfig overrides
    tknobs = {k: overrides.pop(k) for k in
              ("grad_accum_dtype", "shard_grad_accum", "grad_compress")
              if k in overrides}
    if "shard_grad_accum" in tknobs:
        tknobs["shard_grad_accum"] = bool(int(tknobs["shard_grad_accum"]))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    sharding.set_mesh(mesh)

    if shape.kind == "train":
        mb = microbatches if microbatches is not None else \
            MICROBATCHES.get(arch, 1)
        tcfg = ts_mod.TrainConfig(microbatches=mb, **tknobs)
        params_abs = specs_mod.params_abstract(cfg)
        opt_abs = jax.eval_shape(opt_mod.init_opt_state, params_abs)
        batch_abs = specs_mod.input_specs(cfg, shape)
        psh, opt_sh, _ = ts_mod.shardings_for(cfg, mesh,
                                              params_abstract=params_abs)
        bsh = _batch_shardings(mesh, batch_abs)
        step = ts_mod.make_train_step(cfg, tcfg, mesh)
        lowered = jax.jit(step, in_shardings=(psh, opt_sh, bsh)).lower(
            params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        params_abs = specs_mod.params_abstract(cfg, dtype=cfg.dtype)
        batch_abs = specs_mod.input_specs(cfg, shape)
        psh = _named(mesh, sharding.param_specs(params_abs, mesh))
        bsh = _batch_shardings(mesh, batch_abs)
        fn = serve_engine.make_prefill(cfg, shape.seq_len, mesh)
        lowered = jax.jit(fn, in_shardings=(psh, bsh)).lower(
            params_abs, batch_abs)
    else:                                               # decode
        B = shape.global_batch
        params_abs = specs_mod.params_abstract(cfg, dtype=cfg.dtype)
        cache_abs = specs_mod.cache_abstract(cfg, B, shape.seq_len)
        batch_abs = specs_mod.input_specs(cfg, shape)
        psh = _named(mesh, sharding.param_specs(params_abs, mesh))
        csh = _named(mesh, sharding.cache_specs(cache_abs, mesh, B))
        bsh = _batch_shardings(mesh, batch_abs)
        fn = serve_engine.make_serve_step(cfg, mesh)
        lowered = jax.jit(fn, in_shardings=(psh, csh, bsh)).lower(
            params_abs, cache_abs, batch_abs)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):                # jax 0.4.x returns [dict]
        cost = cost[0] if cost else {}
    stats = analyze(compiled.as_text())   # loop-trip-corrected (per device)
    n_dev = mesh.devices.size
    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "n_devices": n_dev,
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 1),
        "flops": stats["flops"],
        "bytes_accessed": stats["bytes"],
        "collective_bytes": stats["collective_bytes"],
        "tagged_bytes": stats.get("tagged_bytes", {}),
        "unknown_trip_whiles": stats["unknown_trip_whiles"],
        # raw XLA numbers (while bodies counted once — see hlo_analyzer.py)
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes",
                                      getattr(mem, "temp_size_in_bytes", 0))),
        },
        "model": {
            "n_params": get_config(arch).n_params(),
            "n_active_params": get_config(arch).n_active_params(),
        },
        "overrides": dict(overrides, **tknobs),
    }
    return lowered, compiled, meta


def run_cma_dryrun(mesh, multi_pod: bool):
    """Lower the CMA-ES K-Distributed strategy step on the production mesh —
    the paper's technique as a first-class dry-run cell."""
    from repro.core.strategies import KDistributed
    from repro.fitness import bbob

    n_dev = mesh.devices.size
    inst = bbob.make_instance(8, 40, 1)
    fit = lambda X: bbob.evaluate(8, inst, X)
    kd = KDistributed(n=40, n_devices=n_dev, lam_start=12, dtype="float64")
    lowered = kd.lower_step(mesh, fit, chunk=1)
    t0 = time.time()
    compiled = lowered.compile()
    stats = analyze(compiled.as_text())
    return {
        "arch": "cma-kdistributed-f8-d40", "shape": "gen_step",
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "n_devices": n_dev, "kind": "cma",
        "compile_seconds": round(time.time() - t0, 1),
        "flops": stats["flops"],
        "bytes_accessed": stats["bytes"],
        "collective_bytes": stats["collective_bytes"],
        "memory": {}, "model": {},
    }


def run_gen_kernel_dryrun(mesh, multi_pod: bool):
    """Lower the slot-batched fused generation megakernels
    (kernels/cma_gen.py — sample + update, one slot per ladder rung) at the
    paper's n = 40 geometry as a first-class dry-run cell.  On TPU
    toolchains this exercises the Mosaic lowering; elsewhere the interpret
    lowering still yields the roofline flops/bytes of the fused path."""
    import jax.numpy as jnp

    from repro.core import cmaes, ladder

    eng = ladder.LadderEngine(n=40, lam_start=12, kmax_exp=4,
                              schedule="concurrent", impl="pallas",
                              dtype="float64")
    carry = eng.init_carry(jax.random.PRNGKey(0))
    S, lam_max, n = eng.n_slots, eng.lam_max, eng.n
    Z_abs = jax.ShapeDtypeStruct((S, lam_max, n), eng.cfg.jdtype)

    def mega(states, Z):
        Y, X = cmaes.kops.gen_sample(states.m, states.sigma, states.B,
                                     states.D, Z, impl="pallas")
        W = jnp.ones((S, lam_max), eng.cfg.jdtype) / lam_max
        from repro.core.params import select_params
        params_k = select_params(eng.sparams, jnp.arange(S))
        coef = cmaes.gen_coef(params_k, states)
        return cmaes.kops.gen_update(states.C, states.B, states.D,
                                     states.p_sigma, states.p_c, Y, W, coef,
                                     impl="pallas")

    lowered = jax.jit(mega).lower(
        jax.eval_shape(lambda c: c.states, carry), Z_abs)
    t0 = time.time()
    compiled = lowered.compile()
    stats = analyze(compiled.as_text())
    return {
        "arch": "cma-genmegakernel-d40", "shape": "slots_gen_step",
        "mesh": "1", "n_devices": 1, "kind": "cma",
        "compile_seconds": round(time.time() - t0, 1),
        "flops": stats["flops"],
        "bytes_accessed": stats["bytes"],
        "collective_bytes": stats["collective_bytes"],
        "memory": {}, "model": {},
        "engine": {"slots": S, "lam_max": lam_max, "n": n,
                   "impl": "pallas"},
    }


def run_mesh_engine_dryrun(mesh, multi_pod: bool):
    """Lower one shard_map segment of the mesh campaign engine (S1 ordered,
    widest rung bucket, one member per device) with the production mesh's
    devices re-viewed as a flat ("camp",) campaign axis — the paper's actual
    deployment (distributed/mesh_engine.py) as a first-class dry-run cell.
    The psum/all_gather carry reduction shows up in ``collective_bytes``."""
    from repro.distributed import mesh_engine
    from repro.launch.mesh import make_campaign_mesh

    camp = make_campaign_mesh(devices=mesh.devices.flat)
    eng = mesh_engine.MeshCampaignEngine(
        n=40, lam_start=12, kmax_exp=4, max_evals=200_000,
        eigen_interval=5, mesh=camp)
    lowered, geo = mesh_engine.lower_ordered_segment(eng, fid=8, seg_blocks=1)
    t0 = time.time()
    compiled = lowered.compile()
    stats = analyze(compiled.as_text())
    return {
        "arch": "cma-meshcampaign-f8-d40", "shape": "segment",
        "mesh": "x".join(map(str, camp.devices.shape)),
        "n_devices": int(camp.devices.size), "kind": "cma",
        "compile_seconds": round(time.time() - t0, 1),
        "flops": stats["flops"],
        "bytes_accessed": stats["bytes"],
        "collective_bytes": stats["collective_bytes"],
        "memory": {}, "model": {}, "engine": geo,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--cma", action="store_true",
                    help="also dry-run the CMA-ES strategy step")
    ap.add_argument("--out-dir", default=ARTIFACT_DIR)
    ap.add_argument("--suffix", default="",
                    help="artifact-name suffix for §Perf variants")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VAL", help="ModelConfig override, e.g. "
                    "--set attn_impl=flash --set microbatches=2")
    args = ap.parse_args(argv)

    overrides: dict = {}
    microbatches = None
    for kv in args.set:
        k, v = kv.split("=", 1)
        if k == "microbatches":
            microbatches = int(v)
            continue
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    os.makedirs(args.out_dir, exist_ok=True)
    mesh = mesh_mod.make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "pod"

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in cells_for(arch):
                cells.append((arch, shape))
    elif args.arch and args.shape:
        cells.append((args.arch, args.shape))
    elif not args.cma:
        ap.error("--arch/--shape, --all, or --cma required")

    failures = []
    for arch, shape in cells:
        name = f"{arch}__{shape}__{tag}{args.suffix}"
        try:
            _, _, meta = lower_cell(arch, shape, mesh,
                                    microbatches=microbatches,
                                    overrides=overrides or None)
            with open(os.path.join(args.out_dir, name + ".json"), "w") as f:
                json.dump(meta, f, indent=1)
            print(f"OK   {name}  flops={meta['flops']:.3e} "
                  f"coll={meta['collective_bytes']['total']:.3e}B "
                  f"compile={meta['compile_seconds']}s", flush=True)
        except Exception as e:
            failures.append((name, e))
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()

    n_extra = 0
    if args.cma:
        for name, runner in ((f"cma__kdist__{tag}", run_cma_dryrun),
                             (f"cma__meshcampaign__{tag}",
                              run_mesh_engine_dryrun),
                             (f"cma__genkernel__{tag}",
                              run_gen_kernel_dryrun)):
            n_extra += 1
            try:
                meta = runner(mesh, args.multi_pod)
                with open(os.path.join(args.out_dir, name + ".json"),
                          "w") as f:
                    json.dump(meta, f, indent=1)
                print(f"OK   {name}  flops={meta['flops']:.3e} "
                      f"coll={meta['collective_bytes']['total']:.3e}B",
                      flush=True)
            except Exception as e:
                failures.append((name, e))
                print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()

    print(f"\n{len(cells) + n_extra - len(failures)} ok, "
          f"{len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
