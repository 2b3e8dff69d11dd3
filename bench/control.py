"""Read the benchmark's control on the chip, beside the program.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 \
        [--seconds 51]

For each seed, one run of the cell as ``run_cell.py`` makes it (set-up,
the measured window, the program's frames against the plain reference),
then the same sampled session windows computed by the reference at three
bfloat16 passes (the precision below the float32 the configuration
states) put in the program's place, judged by the cell's own limits.
Prints one JSON line per seed: ``{"seed", "program": readings,
"correct", "control": readings, "control_checks", "control_correct"}``;
``control_correct`` has to come out false. The limits in
``bench/limits/<cell>.json`` are set between the program's largest
reading and the control's smallest. Runs on the chip only; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    import jax

    cell = run_cell.load_cell(args.workload)
    run_cell.enable_cache(jax)
    try:
        run_cell.check_device(cell["chips"])
    except run_cell.NoChip as e:
        run_cell.log(f"control: {e}")
        return 2
    for seed in args.seeds:
        r = run_cell.run(cell, seed, args.seconds, trace=False,
                         control="high")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": r["readings"], "correct": r["correct"],
                          "control": r["control"],
                          "control_checks": r["control_checks"],
                          "control_correct": r["control_correct"],
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
