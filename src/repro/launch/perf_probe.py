import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS", ""), os.environ.get("REPRO_XLA_EXTRA", ""),
    "--xla_force_host_platform_device_count=" +
    os.environ.get("REPRO_DRYRUN_DEVICES", "512"))))

"""Per-op cost attribution for one (arch, shape) combo -- the §Perf profile.

    PYTHONPATH=src python -m repro.launch.perf_probe --arch yi-34b \
        --shape train_4k --metric bytes --top 20
"""
import argparse  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES  # noqa: E402
from repro.launch.dryrun import build_lowered  # noqa: E402
from repro.launch.hlo_cost import analyze_hlo, top_contributors  # noqa: E402
from repro.mesh import make_mesh, make_production_mesh  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--metric", default="bytes",
                    choices=["bytes", "flops", "coll"])
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat-chunk", action="store_true")
    ap.add_argument("--shard-acts", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--cp-cache", action="store_true")
    ap.add_argument("--small-out", type=int, default=0)
    ap.add_argument("--describe", action="store_true",
                    help="print the sharding plan (param -> PartitionSpec)")
    args = ap.parse_args()

    if args.mesh:
        mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    if args.describe:
        from repro.configs import get_config
        from repro.mesh import describe_shardings, param_shardings
        from repro.models import param_specs
        cfg = get_config(args.arch)
        specs = param_specs(cfg)
        sh = param_shardings(specs, mesh, small_out_threshold=args.small_out)
        for name, shape, spec in describe_shardings(specs, sh):
            print(f"{name:48s} {str(shape):28s} {spec}")
        return

    with mesh:
        lowered, why = build_lowered(
            args.arch, args.shape, mesh, remat_chunk=args.remat_chunk,
            shard_acts=args.shard_acts, seq_shard=args.seq_shard,
            cp_cache=args.cp_cache, small_out=args.small_out)
        if lowered is None:
            raise SystemExit(f"skipped: {why}")
        compiled = lowered.compile()
    txt = compiled.as_text()
    cost = analyze_hlo(txt)
    print(f"total flops/dev {cost.flops:.3e}  bytes/dev {cost.bytes:.3e}  "
          f"coll/dev {cost.coll_bytes:.3e}")
    print(f"collective breakdown: "
          f"{ {k: f'{v:.2e}' for k, v in cost.coll_breakdown.items()} }")
    print(f"\ntop {args.top} by {args.metric}:")
    for v, comp, op, name, shape in top_contributors(txt, args.metric,
                                                     args.top):
        print(f"{v:.3e}  {op:22s} {shape:60s} in {comp[:40]} ({name[:40]})")


if __name__ == "__main__":
    main()
