"""Readings that set the limits of a cell's correctness check.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --seconds 8 \
        [--variants program,control] [--seed-list 11,22,...]

Runs the cell, for each seed and each variant, in this one process (so
set-up compiles once): ``program``, as the benchmark runs it; ``control``,
which must come out not correct (the configuration's ``control``: the
reference at the next precision below in the program's place); or the
name of a fault, planted under the program: one of the kind's own
(``faults_<kind>.py``) or a shared one (``faults.py``).  A short window
at the cell's own load is enough to produce the answers the check
compares.  Prints one line per run with every number the check reads,
compared or not, and the largest and smallest reading of each number per
variant, which bracket the limits.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import faults  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seed-list", default="",
                    help="comma-separated seeds, read in place of --seeds/--first-seed")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    kind = cell["config_data"]["kind"]
    run.add_paths()
    try:
        devices = run.require_chips(int(cell["chips"]))
    except run.NoChip as exc:
        run.log(str(exc))
        return 2
    run.configure_jax()
    seeds = ([int(x) for x in args.seed_list.split(",")] if args.seed_list else
             [args.first_seed + 7919 * i for i in range(args.seeds)])
    readings: dict = {}
    for variant in args.variants.split(","):
        for seed in seeds:
            vals: dict = {}
            if variant in faults.names(kind):
                with faults.planted(variant, kind=kind):
                    out = run.run_cell(cell, seed, args.seconds, False, devices,
                                       readings=vals)
            else:
                out = run.run_cell(cell, seed, args.seconds, False, devices, variant,
                                   readings=vals)
            vals["window_compiles"] = out["checks"]["window_compiles"]["value"]
            print(json.dumps({"variant": variant, "seed": seed,
                              "correct": out["correct"], "checks": vals}), flush=True)
            for k, v in vals.items():
                readings.setdefault((variant, k), []).append(v)
    summary = {}
    for (variant, k), vs in sorted(readings.items()):
        summary[f"{variant}.{k}"] = {"max": max(vs), "min": min(vs)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
