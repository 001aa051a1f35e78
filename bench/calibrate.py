#!/usr/bin/env python3
"""Readings that set a cell's limits: the compared numbers of the served
path and of the control, over many seeds, in one process on the chip.

  python3 bench/calibrate.py --workload vit-l384.4g-pruned --seconds 3 \\
      --seeds 11 12 13 14 15 16 17 18 19 20 21 22 --out cal.json

For each seed it makes that seed's weights and frames, puts them into one
fleet runtime built once (so only the first seed compiles), runs a window
of ``--seconds`` at the cell's own load, and compares the drawn ticks'
frames with the float32 reference, as a benchmark run does. The control is
the reference in the precision the cell's limits file names (the step below
the configuration's bfloat16) put in the served path's place: for the same frames,
(image, alpha, split), its logits are compared with the float32 reference.
A limit lies above the largest reading of the served path and below the
smallest of the control. The benchmark's runs do not run this.
"""
import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def set_weights(sv, params, images):
    sv.rt.params = params
    for e in sv.rt.engines:
        e.params = params
    sv.params, sv.images = params, images


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", nargs="*", default=[],
                    help="precisions of the control (default: the cell's limits file's)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("[calibrate] no TPU", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    fam, c = cell.family, cell.config
    controls = args.control or [cell.limits["control"]]
    sv = harness.build(cell, args.seeds[0])
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        if rows:
            fresh = harness.build(cell, seed)
            set_weights(sv, fresh.params, fresh.images)
            del fresh
        for i in range(harness.WARMUP_TICKS):
            harness.tick(sv.rt, sv.images[i % len(sv.images)])
        win = harness.measure(sv.rt, sv.images, args.seconds, seed)
        frames = win.checked()
        served = harness.compare(cell, sv.params, sv.images, frames)
        stack = jax.numpy.concatenate(sv.images, axis=0)
        control = {p: harness.compare(
            cell, sv.params, sv.images, frames,
            against=lambda a, s, p=p: fam.logits(sv.params, c, stack, a, s, p))
            for p in controls}
        row = {"seed": seed, "ticks": len(win.ticks), "frames_checked": len(frames),
               "served": served, "control": control,
               "wall_s": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = [k for k in rows[0]["served"] if k not in ("missing", "nonfinite")]
    summary = {"workload": args.workload, "seeds": args.seeds,
               "served_max": {k: max(r["served"][k] for r in rows) for k in keys},
               "control_min": {p: {k: min(r["control"][p][k] for r in rows) for k in keys}
                               for p in controls},
               "rows": rows}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
