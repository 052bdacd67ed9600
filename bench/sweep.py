"""Find a fixed-rate cell's knee on the chip: one process serves the cell
at each offered rate for ``--seconds`` and prints the tails, the tokens
per second and the requests still queued at the window's close (a queue
that grows with the window means the rate is past what the system
sustains).  The output correctness check is skipped here.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates <r>... \
        [--seed <n>] [--out <file.jsonl>]
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import run
    cell, _, peak, drive = run.prepare(args.workload)
    out = open(args.out, "a") if args.out else None
    for rate in args.rates:
        cell.mix["arrival"]["rate"] = rate
        res = drive.run(cell, args.seed, args.seconds, False,
                        time.monotonic(), peak, check=False)
        line = {"workload": args.workload, "rate": rate,
                "seconds": args.seconds, "e2e": res["e2e"],
                "info": res["info"], "attempted": res["attempted"],
                "failed": res["failed"]}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
