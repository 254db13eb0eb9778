"""Read a cell's numbers for the program, the control and planted faults.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults half_batch --fault-seeds 7,8,9]

One process sets the cell up once.  For each of ``--seeds`` it drives the
program's timed path (a whole grid, or the trainer's first write events)
and prints every number the check can compare, whether the cell's limits
name it or not; for each of ``--control-seeds`` it puts the control in
the program's place (the plain reference computed in the precision below
the configuration's: bfloat16 for float32, float8 for bfloat16) and
prints the same numbers; ``--faults`` does the same with each of the
surface's ``FAULTS`` it names planted in the program.  A cell's limits
(its traffic file) lie between the two: above the largest reading of the
program, below the smallest of the control.  Needs the chip, like a run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="read --fault-seeds with each of these faults of "
                         "the surface's FAULTS planted in the program")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [int(s) for s in args.fault_seeds.split(",") if s]
    cell = harness.Cell(args.workload)
    try:
        device = harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    surface = cell.surface()
    state = surface.setup(cell.config, cell.traffic, (seeds + controls)[0])
    print(json.dumps({"device": device,
                      "setup_s": time.perf_counter() - T_START}), flush=True)
    for side, seed in [("program", s) for s in seeds] + [
            ("control", s) for s in controls]:
        got = surface.readings(
            state, seed, None if side == "program" else surface.CONTROL)
        print(json.dumps({"side": side, "seed": seed, "numbers": got}),
              flush=True)
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in faults:
            undo = surface.FAULTS[fault]()
            try:
                got = surface.readings(state, seed)
            finally:
                undo()
            print(json.dumps({"side": f"fault:{fault}", "seed": seed,
                              "numbers": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
