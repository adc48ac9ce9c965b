"""Readings of a cell's comparison for the program and for its control,
at the cell's own size, on several seeds in one process:

    python3 bench/control.py --workload jsc-mlp-s10.eval --seeds 3,4,5 \
        --seconds 10

For each seed the cell runs its set-up and a window of ``--seconds``,
then compares the sampled answers twice: the program's (the lower
reading of each number) and the control's, the plain reference in the
step below what the configuration states (timing in float32
picoseconds instead of integer centi-picoseconds; evaluation on 16-bit
instead of 32-bit lane words), which has to come out not correct (the
upper reading).  One JSON line per seed.  The benchmark's own runs
never run the control.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness
    from bench.circuits import Designs

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec(ROOT)
    wl, cfg = harness.find_cell(spec, args.workload)
    harness.set_cache_env(ROOT)
    from bench.chip import tpu_devices

    if tpu_devices(int(wl["chips"])) is None:
        return 3
    traffic = harness.load_json(harness.traffic_path(ROOT, wl["traffic"]))
    kind = harness.load_kind(traffic["kind"])
    designs = Designs(harness.load_json(os.path.join(ROOT, cfg["file"])))

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        cell = kind.Cell(designs=designs, traffic=traffic, seed=seed,
                         log=log)
        cell.setup(args.seconds)
        run = harness.Run(workload=args.workload, traffic=traffic)
        out = cell.window(args.seconds, run)
        cell.release()
        program = {c.name: c.value for c in cell.check()}
        control = {c.name: c.value for c in cell.check(control=True)}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "attempted": out["attempted"],
                          "failed": out["failed"], "program": program,
                          "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
