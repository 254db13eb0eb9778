import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS", ""), os.environ.get("REPRO_XLA_EXTRA", ""),
    "--xla_force_host_platform_device_count=" +
    os.environ.get("REPRO_DRYRUN_DEVICES", "512"))))

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh(es) and record memory / cost / collective analyses.

MUST be run as a script / module (the XLA_FLAGS line above executes before
any jax import -- jax locks the device count at first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-780m \
        --shape decode_32k --mesh 2x4        # reduced mesh (tests)

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config, input_specs  # noqa: E402
from repro.configs.shapes import cache_len, decode_window, uses_ring  # noqa: E402
from repro.mesh import dp_size, make_mesh, make_production_mesh  # noqa: E402
from repro.launch.roofline import (model_flops, parse_collective_bytes)  # noqa: E402
from repro.mesh import (batch_shardings, cache_shardings,  # noqa: E402
                                   param_shardings)
from repro.launch.steps import make_prefill_step, make_serve_step, make_trainer  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

DEFAULT_OUT = "experiments/dryrun"


def mesh_tag(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def build_lowered(arch: str, shape_name: str, mesh, *, fsdp: bool = True,
                  remat_chunk: bool = False, shard_acts: bool = False,
                  seq_shard: bool = False, cp_cache: bool = False,
                  small_out: int = 0, ce_chunk: int = 0):
    """Construct and lower the step for one (arch, shape, mesh) combo.

    The keyword knobs are the §Perf beyond-paper optimizations; all default
    OFF so the recorded baseline stays the paper-faithful configuration."""
    from repro.mesh import dp_axes
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return None, why
    if remat_chunk or shard_acts or seq_shard or ce_chunk:
        cfg = cfg.replace(remat_chunk=remat_chunk,
                          shard_activations=shard_acts,
                          seq_shard=seq_shard,
                          ce_chunk=ce_chunk,
                          act_dp_axes=tuple(dp_axes(mesh)))
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        trainer = make_trainer(cfg, n_workers=dp_size(mesh))
        state_specs = trainer.state_specs()
        p_sh = param_shardings(state_specs.params, mesh, fsdp=fsdp,
                               small_out_threshold=small_out)
        o_sh = param_shardings(state_specs.opt.inner, mesh, fsdp=fsdp,
                               small_out_threshold=small_out)
        from repro.launch.steps import TrainState
        from repro.optim.optimizers import DelayAdaptiveState
        opt_sh = DelayAdaptiveState(
            step=NamedSharding(mesh, P()),
            ss=jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()),
                                      state_specs.opt.ss),
            inner=o_sh,
            worker_stamp=NamedSharding(mesh, P()),
        )
        state_sh = TrainState(params=p_sh, opt=opt_sh)
        b_sh = batch_shardings(specs["batch"], mesh, shape.global_batch)
        w_sh = NamedSharding(mesh, P())
        step = trainer.train_step
        lowered = jax.jit(
            step,
            in_shardings=(state_sh, b_sh, w_sh),
            out_shardings=(state_sh, None),
        ).lower(state_specs, specs["batch"], jax.ShapeDtypeStruct((), jnp.int32))
        return lowered, ""

    from repro.models import param_specs as _pspecs
    pspecs = _pspecs(cfg)
    p_sh = param_shardings(pspecs, mesh, fsdp=fsdp,
                           small_out_threshold=small_out)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        b_sh = batch_shardings(specs["batch"], mesh, shape.global_batch)
        lowered = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(
            pspecs, specs["batch"])
        return lowered, ""

    # decode / decode_long
    window = decode_window(cfg, shape)
    ring = uses_ring(cfg, shape)
    step = make_serve_step(cfg, window=window, ring=ring)
    c_sh = cache_shardings(specs["cache"], mesh, shape.global_batch,
                           cache_len(cfg, shape), context_parallel=cp_cache)
    t_sh = batch_shardings(specs["token"], mesh, shape.global_batch)
    lowered = jax.jit(
        step,
        in_shardings=(p_sh, c_sh, t_sh, NamedSharding(mesh, P())),
        out_shardings=(None, c_sh),
    ).lower(pspecs, specs["cache"], specs["token"],
            jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, ""


def analyze(lowered, compiled, cfg_arch: str, shape_name: str, mesh) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(cfg_arch)
    chips = mesh.size
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    # raw XLA numbers under-count while-loop bodies (counted once); the
    # while-aware HLO cost model recovers exact per-step totals.
    flops_raw = float(cost.get("flops", 0.0))
    nbytes_raw = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    from repro.launch.hlo_cost import analyze_hlo
    adj = analyze_hlo(hlo)
    flops = float(adj.flops)
    nbytes = float(adj.bytes)
    coll = {k: float(v) for k, v in adj.coll_breakdown.items()}
    coll_counts = parse_collective_bytes(hlo).pop("_counts")
    coll_total = float(adj.coll_bytes)
    mem = compiled.memory_analysis()
    memd = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        memd[attr] = int(getattr(mem, attr, 0) or 0)
    mf = model_flops(cfg, shape)
    return {
        "arch": cfg_arch,
        "shape": shape_name,
        "mesh": mesh_tag(mesh),
        "chips": chips,
        "flops_per_device_xla_raw": flops_raw,
        "hbm_bytes_per_device_xla_raw": nbytes_raw,
        "flops_per_device": flops,
        "hbm_bytes_per_device": nbytes,
        "collective_bytes_per_device": coll_total,
        "collective_breakdown": coll,
        "collective_counts": coll_counts,
        "memory": memd,
        "model_flops_total": mf,
    }


def run_one(arch: str, shape_name: str, mesh, out_dir: str, *,
            fsdp: bool = True, tag: str = "", verbose: bool = True,
            **knobs) -> dict:
    t0 = time.time()
    lowered, why = build_lowered(arch, shape_name, mesh, fsdp=fsdp, **knobs)
    if lowered is None:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag(mesh),
               "skipped": why}
        _write(out_dir, rec, tag)
        if verbose:
            print(f"SKIP  {arch} x {shape_name} x {mesh_tag(mesh)}: {why}")
        return rec
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    rec = analyze(lowered, compiled, arch, shape_name, mesh)
    rec["t_lower_s"] = t_lower
    rec["t_compile_s"] = t_compile
    _write(out_dir, rec, tag)
    if verbose:
        mb = rec["memory"]
        per_dev_gb = (mb["argument_size_in_bytes"] + mb["temp_size_in_bytes"] +
                      mb["output_size_in_bytes"]) / 2**30
        print(f"OK    {arch} x {shape_name} x {mesh_tag(mesh)}  "
              f"flops/dev={rec['flops_per_device']:.3e} "
              f"coll/dev={rec['collective_bytes_per_device']:.3e}B "
              f"mem(arg+tmp+out)={per_dev_gb:.2f}GiB  "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
    return rec


def _write(out_dir: str, rec: dict, tag: str = "") -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    if tag:
        name += f"__{tag}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="all arch x shape")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16")
    ap.add_argument("--mesh", default=None,
                    help="override mesh, e.g. 2x4 (data x model) or 2x2x2")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    # §Perf beyond-paper knobs (baseline = all off)
    ap.add_argument("--remat-chunk", action="store_true")
    ap.add_argument("--shard-acts", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--cp-cache", action="store_true")
    ap.add_argument("--small-out", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--opt", action="store_true",
                    help="enable the full optimized bundle")
    args = ap.parse_args()
    if args.opt:
        args.remat_chunk = args.shard_acts = args.cp_cache = True
        args.small_out = args.small_out or 1024
        if not args.tag:
            args.tag = "opt"
    knobs = dict(remat_chunk=args.remat_chunk, shard_acts=args.shard_acts,
                 seq_shard=args.seq_shard, cp_cache=args.cp_cache,
                 small_out=args.small_out, ce_chunk=args.ce_chunk)

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(dims)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        combos = [(args.arch, args.shape)]

    failures = []
    with mesh:
        for a, s in combos:
            try:
                run_one(a, s, mesh, args.out, fsdp=not args.no_fsdp,
                        tag=args.tag, **knobs)
            except Exception as e:  # pragma: no cover
                failures.append((a, s, repr(e)))
                print(f"FAIL  {a} x {s}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run combos failed: {failures}")


if __name__ == "__main__":
    main()
