import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) combination, build the production
mesh, attach the sharding rules, ``jit(...).lower(...).compile()`` the
right step function (train_step / prefill / serve_step), and record
``memory_analysis`` + ``cost_analysis`` + the collective schedule parsed
from the post-SPMD HLO.  Results land as JSON under
``results/dryrun/<mesh>/<arch>__<shape>.json`` (incremental: existing
files are skipped unless --force).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, input_specs, params_struct, variant_for_shape
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding import rules
from repro.training.optimizer import OptConfig
from repro.training.train import make_train_step, train_state_struct

def build_case(arch: str, shape_name: str, mesh, *, opt_overrides=None,
               optimized: bool = False):
    """Returns (fn, args tuple, in_shardings tuple).

    ``optimized=True`` applies the §Perf hillclimb changes: head->seq
    sharding fallback and the inference weight-sharding profile for decode.
    """
    cfg = variant_for_shape(get_config(arch), SHAPES[shape_name])
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    pstruct = params_struct(cfg)
    profile = "inference" if (optimized and shape.kind == "decode") else "train"
    pspec = rules.param_specs(pstruct, mesh, profile=profile)
    shard_fn = rules.make_shard_fn(mesh, head_seq_fallback=optimized)

    if shape.kind == "train":
        opt_cfg = OptConfig(moment_dtype="bfloat16", master_fp32=False,
                            **(opt_overrides or {}))
        _, ostruct = train_state_struct(cfg, opt_cfg)
        ospec = {"m": pspec, "v": pspec,
                 "t": jax.sharding.PartitionSpec()}
        step = make_train_step(cfg, opt_cfg, shard_fn=shard_fn)
        bspec = rules.batch_specs(specs["batch"], mesh)
        return (step, (pstruct, ostruct, specs["batch"]),
                (pspec, ospec, bspec), (pspec, ospec, None))

    if shape.kind == "prefill":
        def step(params, batch):
            logits, cache, pos = T.prefill(params, cfg, batch, shape.seq_len,
                                           shard_fn=shard_fn)
            return logits, cache
        bspec = rules.batch_specs(specs["batch"], mesh)
        return step, (pstruct, specs["batch"]), (pspec, bspec), None

    # decode
    def step(params, cache, token, pos):
        return T.serve_step(params, cfg, cache, token, pos, shard_fn=shard_fn)
    cspec = rules.cache_specs(specs["cache"], mesh)
    P = jax.sharding.PartitionSpec
    tspec, posspec = rules.batch_specs(specs["token"], mesh), P()
    return (step, (pstruct, specs["cache"], specs["token"], specs["pos"]),
            (pspec, cspec, tspec, posspec), None)


def run_case(arch: str, shape_name: str, *, multi_pod: bool = False,
             outdir: str = "results/dryrun", force: bool = False,
             save_hlo: bool = False, builder=build_case,
             optimized: bool = False) -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(f"{outdir}/{mesh_tag}", exist_ok=True)
    path = f"{outdir}/{mesh_tag}/{arch}__{shape_name}.json"
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh, out_sh = (lambda r: (r + (None,) * (4 - len(r))))(
        builder(arch, shape_name, mesh, optimized=optimized))
    in_shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), in_sh,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    out_shardings = None
    if out_sh is not None:
        out_shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), out_sh,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import HloCost
    hc = HloCost(hlo)
    by_op = hc.collective_summary()
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "devices": int(len(mesh.devices.flatten())),
        "time_lower_s": round(t_lower, 2), "time_compile_s": round(t_compile, 2),
        # trip-count-corrected per-device costs (see hlo_cost.py; XLA's own
        # cost_analysis counts while bodies once)
        "flops_per_device": hc.flops,
        "bytes_per_device": hc.bytes,
        "xla_flops_per_device_raw": ca.get("flops"),
        "xla_bytes_accessed_raw": ca.get("bytes accessed"),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        },
        "collectives": by_op,
        "collective_wire_bytes_total": sum(d["wire_bytes"] for d in by_op.values()),
        "n_collective_sites": len(hc.collectives),
        # HBM bytes inside named kernel-replaceable scopes (flash_attention,
        # wkv_scan): the Pallas kernels keep this traffic in VMEM on TPU
        "scope_bytes": hc.scope_bytes,
    }
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--opt", action="store_true",
                    help="apply §Perf optimizations (writes to --outdir; "
                         "use a distinct outdir to keep baselines)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    failures = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{'2x16x16' if mp else '16x16'} {arch:22s} {shape:12s}"
                try:
                    r = run_case(arch, shape, multi_pod=mp, force=args.force,
                                 outdir=args.outdir, save_hlo=args.save_hlo,
                                 optimized=args.opt)
                    print(f"OK   {tag} compile={r['time_compile_s']:7.1f}s "
                          f"flops/dev={r['flops_per_device']:.3e} "
                          f"peak={r['memory']['peak_estimate_bytes']/2**30:.2f}GiB "
                          f"wire={r['collective_wire_bytes_total']/2**20:.1f}MiB",
                          flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
