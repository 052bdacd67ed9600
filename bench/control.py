"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n>... \
        [--control-seeds <k>] [--out <file.jsonl>]

For each seed it makes one run of the cell as the benchmark does, at the
cell's own load and sizes, and prints the widest logit gap of the served
tokens (the lower reading comes from the largest over the seeds).  For the
first ``--control-seeds`` seeds it also reads the float8 control at the
same positions of the same requests (the upper reading is the smallest of
those) and judges the control's tokens by the cell's own limits, by the
rule every run uses: its ``correct`` has to come out false.  All seeds run
in one process.  The benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.dont_write_bytecode = True
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import run
    cell, _, peak, drive = run.prepare(args.workload)
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        t = time.monotonic()
        res = drive.run(cell, seed, args.seconds, False, t, peak,
                        control=i < args.control_seeds)
        line = {"workload": args.workload, "seed": seed,
                "seconds": args.seconds, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "checks": res["checks"], "control": res.get("control"),
                "info": res["info"],
                "e2e": res["e2e"],
                "memory_peak_bytes": res["memory_peak_bytes"],
                "wall_s": time.monotonic() - t}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
