"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration x traffic mix) is looked up in ``BENCHMARK.json``;
everything it needs is found by name under ``bench/`` (see ``spec.py``).
Set-up (imports, weights, compiles or compile-cache loads, warm-up) is
timed as ``setup_s``; then the window runs for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiled run.

The last line of standard output is one JSON object; the numbers the
correctness check compared, each beside its limit, end standard error and
that object.  Without an accelerator, or with fewer chips than the cell
asks for, it exits non-zero before printing any result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.dont_write_bytecode = True
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path of the checkout,
    for every program however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_device(chips: int):
    """The accelerator this run measures, or SystemExit."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def prepare(workload: str):
    """(cell, devices, peaks, the module that runs it) of a cell, on this
    machine's chips."""
    from bench import spec
    cell = spec.cell(ROOT, workload)
    enable_compile_cache()
    devs = find_device(cell.chips)
    return cell, devs, spec.peak(ROOT, devs[0].device_kind), \
        spec.mode_module(cell.mix["mode"])


def metric_line(value, unit):
    return {"value": value, "unit": unit}


def result_line(cell, res, devs, traced: bool) -> dict:
    """The result object: the cell's end-to-end metrics (untraced) or its
    per-layer metrics (traced), the device, and the compared numbers."""
    metrics = {}
    if traced:
        for m in cell.per_layer:
            v = cell.readers[m["name"]](res["obs"])
            if v is not None:
                metrics[m["name"]] = metric_line(v, m["unit"])
    else:
        for m in cell.end_to_end:
            if m["name"] not in res["e2e"]:
                raise SystemExit(f"the run measured no {m['name']}")
            metrics[m["name"]] = metric_line(res["e2e"][m["name"]], m["unit"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if traced:
        tr = res["obs"].trace
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = res["checks"]
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell, devs, peak, drive = prepare(args.workload)
    t_dev = time.monotonic()
    res = drive.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                    peak)
    res["info"]["setup"]["to_device_s"] = t_dev - T_START
    out = result_line(cell, res, devs, bool(args.trace))
    for k, v in res.get("info", {}).items():
        print(f"{k} {v}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
