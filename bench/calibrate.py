"""Readings from which a cell's limits are set, many seeds in one process:

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 5 [--out readings.json]

For each seed it runs the cell as ``bench/run.py`` does (set-up, a window of
``--seconds``, the program's outputs), then the reference over them. For a
control seed the fp8 reference runs beside the float32 one and gives the
control's readings. It prints one JSON line per seed and, last, for each
number the lower reading (the largest the program gave) and the upper one
(the smallest the control gave). The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import harness, spec

    ap = argparse.ArgumentParser(prog="python3 bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    cell = spec.load_cell(args.workload, seconds=args.seconds)
    harness.place_compile_cache(ROOT)
    mesh = harness.chip_mesh(cell.chips)
    counter = harness.CompileCounter()
    rows = []
    with mesh:
        for seed in seeds + sorted(control - set(seeds)):
            t0 = time.perf_counter()
            loop = harness.make_loop(cell, mesh, seed)
            loop.setup()
            counter.count = 0
            harness.run_window(loop, args.seconds, counter)
            out = loop.finish()
            precisions = ("f32", "fp8") if seed in control else ("f32",)
            t1 = time.perf_counter()
            readings = loop.check(out, precisions)
            row = {"seed": seed, "steps": loop.steps, "readings": readings,
                   "run_s": t1 - t0, "reference_s": time.perf_counter() - t1}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del loop, out
            gc.collect()
    names = sorted(rows[0]["readings"]["f32"])
    summary = {}
    for k in names:
        lower = max(r["readings"]["f32"][k] for r in rows if r["seed"] in seeds)
        ctrl = [r["readings"]["fp8"][k] for r in rows if "fp8" in r["readings"]]
        summary[k] = {"lower": lower, "upper": min(ctrl) if ctrl else None,
                      "program": [r["readings"]["f32"][k] for r in rows
                                  if r["seed"] in seeds],
                      "control": ctrl}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, "summary": summary},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
