"""Readings that set a cell's logit-gap limit (PERF.md §2), and the proof
that the control fails the run's own comparison.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One process runs the cell's timed path once per seed, exactly as
``bench/run.py`` does, with a shorter window, and reads from the same
served prompts and tokens:

  program   by how much each served token's reference logit lies below
            the reference's best: the widest such gap and the mean one;
  control   the same for the token that the reference computed in float8
            (one step below the configuration's bfloat16) puts first at
            each position. It takes the program's place in the run's
            comparison (``run_cell(control=True)``), so its ``correct``
            is what the harness decides for it, against the cell's limits.

A number's lower reading is the largest the program gives over a dozen
seeds or more, its upper reading the smallest the control gives; a limit
lies between them. One JSON line per seed, then a summary line. Exits 1
when the control comes out correct on any seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.use_compile_cache()
    program, control, control_correct = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(cell, seed, args.seconds, False, control=True)
        except run.NoChip as e:
            run.log(f"control: {e}")
            return 3
        program.append(res["extra"]["readings"])
        control.append(res["extra"]["control"])
        control_correct.append(res["correct"])
        print(json.dumps({"seed": seed, "program": program[-1],
                          "program_correct": res["extra"]["program_correct"],
                          "control": control[-1],
                          "control_correct": res["correct"],
                          "compared": res["compared"],
                          "device": res["device"]["kind"]}), flush=True)
        gc.collect()
    summary = {"workload": args.workload, "seeds": len(program),
               "control_correct_on_any_seed": any(control_correct)}
    for name in program[0]:
        lower = max(p[name] for p in program)
        upper = min(c[name] for c in control)
        summary[name] = {"lower_reading": lower, "upper_reading": upper,
                         "ratio": upper / lower if lower else None}
    print(json.dumps(summary), flush=True)
    return 1 if any(control_correct) else 0


if __name__ == "__main__":
    sys.exit(main())
