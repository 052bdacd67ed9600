import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks the device count on first
#   init).  512 placeholder host devices back both the (16,16) single-pod
#   mesh (auto-subset of 256) and the (2,16,16) multi-pod mesh.

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and extract the roofline raw material.

For each cell this produces (and appends to a JSONL artifact):

* ``memory_analysis``  — per-device argument/output/temp bytes (proves fit),
* ``cost_analysis``    — per-device HLO FLOPs + bytes accessed,
* ``collective_bytes`` — parsed from the post-SPMD HLO: summed per-device
  tensor bytes of all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute ops (cost_analysis does not report these),
* compile wall time and the collective-op census.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_5_3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

``--all`` runs each cell in a subprocess so XLA compiler state cannot leak
across cells (and one failure doesn't kill the sweep).
"""

import argparse
import json
import re
import subprocess
import sys
import time
from pathlib import Path

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[0-9]+)?)\[([0-9,]*)\]")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum per-device result bytes of every collective op in post-SPMD HLO."""
    totals = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        op = None
        for cand in _COLLECTIVES:
            # match " = <shape> all-reduce(" or tuple-shaped results
            if f" {cand}(" in stripped or f"{cand}-start(" in stripped:
                op = cand
                break
        if op is None or "=" not in stripped:
            continue
        lhs = stripped.split("=", 1)[1]
        lhs = lhs.split(op, 1)[0]
        nbytes = 0
        for dtype, dims in _SHAPE_RE.findall(lhs):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dtype]
        totals[op] += nbytes
        counts[op] += 1
    return {
        "bytes_by_op": totals,
        "counts_by_op": counts,
        "total_bytes": sum(totals.values()),
        "total_ops": sum(counts.values()),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_path: str,
             verbose: bool = True, opts: tuple = ()) -> dict:
    import jax

    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape_name, mesh, opts=opts)

    t0 = time.time()
    with mesh:
        jf = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     donate_argnums=cell.donate_argnums)
        lowered = jf.lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    if verbose:
        print(compiled.memory_analysis())   # proves it fits
    cost = compiled.cost_analysis()
    if verbose:
        print({k: cost[k] for k in ("flops", "bytes accessed")
               if k in cost})
    hlo_text = compiled.as_text()
    coll = parse_collective_bytes(hlo_text)
    # Trip-count-aware costs: XLA's cost_analysis counts while (scan) bodies
    # exactly once, underreporting scan-heavy programs by the trip count —
    # repro.launch.hlo_cost re-derives flops/bytes/collective bytes with
    # loop multipliers from the compiled module's known_trip_count configs.
    from repro.launch.hlo_cost import analyze_hlo
    tc = analyze_hlo(hlo_text)

    chips = 1
    for d in mesh.devices.shape:
        chips *= d
    record = {
        "arch": arch,
        "shape": shape_name,
        "opts": sorted(cell.opts),
        "entry": cell.entry,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "axes": list(mesh.axis_names),
        "chips": chips,
        "tokens_per_step": cell.tokens_per_step,
        "model_params": cell.model_cfg.param_count(),
        "model_active_params": cell.model_cfg.active_param_count(),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.temp_size_in_bytes),
        },
        # trip-count-aware (authoritative for §Roofline):
        "cost": {
            "flops": tc.flops,
            "bytes_accessed": tc.bytes,
        },
        "collectives": {
            "bytes_by_op": tc.collective_bytes_by_op,
            "total_bytes": tc.collective_bytes,
            "total_ops": tc.collective_ops,
            "while_loops": tc.while_loops,
            "unknown_trip_loops": tc.unknown_trip_loops,
        },
        # raw single-visit numbers, for reference (scan bodies counted once):
        "xla_cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives_flat": coll,
    }
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(record) + "\n")
    if verbose:
        print(json.dumps({k: record[k] for k in
                          ("arch", "shape", "mesh", "compile_s")}))
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma-separated perf knobs (see specs.build_cell)")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun.jsonl")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    Path(args.out).parent.mkdir(parents=True, exist_ok=True)

    opts = tuple(o for o in args.opt.split(",") if o)
    if not args.all:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        run_cell(args.arch, args.shape, args.multi_pod, args.out, opts=opts)
        return

    # sweep: one subprocess per cell for isolation
    from repro.configs import all_cells
    done = set()
    if args.skip_existing and Path(args.out).exists():
        for line in Path(args.out).read_text().splitlines():
            r = json.loads(line)
            done.add((r["arch"], r["shape"], r["mesh"]))
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    cells = all_cells()
    failures = []
    for i, (arch, shape) in enumerate(cells):
        if (arch, shape, mesh_tag) in done:
            print(f"[{i+1}/{len(cells)}] {arch} x {shape} — cached")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", args.out]
        if args.multi_pod:
            cmd.append("--multi-pod")
        print(f"[{i+1}/{len(cells)}] {arch} x {shape} ({mesh_tag}) ...",
              flush=True)
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            failures.append((arch, shape))
            print(f"  FAILED ({time.time()-t0:.0f}s):\n{proc.stderr[-2000:]}")
        else:
            print(f"  ok ({time.time()-t0:.0f}s)")
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print(f"all {len(cells)} cells passed on {mesh_tag}")


if __name__ == "__main__":
    main()
