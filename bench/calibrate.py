"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 -m bench.calibrate --workload <name> --seeds <n> [<n> ...] \\
        [--seconds S] [--control fp8]

One process runs the cell once per seed, through the timed path as
``bench.run`` does (a short window at the cell's own load), and prints for
each seed one JSON line: the program's widest gap and, on the same sample
of requests, the control's (the reference computed in float8, the precision
below the configuration's bfloat16), each with the ``correct`` that the
comparison gives it.  The lower reading of a limit is the
largest program gap over a dozen seeds or more; the upper, the smallest
control gap.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import run, spec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.fail(f"needs a TPU; JAX found platform {devices[0].platform!r}")
    cell = spec.cell(args.workload)
    lows, highs = [], []
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           control=args.control)
        gap = res["checks"]["widest_gap"]["value"]
        ctl = res["control"]
        lows.append(gap)
        highs.append(ctl["checks"]["widest_gap"]["value"])
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "program_correct": res["correct"],
                          "control_gap": highs[-1],
                          "control_correct": ctl["correct"],
                          "checks": res["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(lows),
                      "upper": min(highs), "seeds": len(args.seeds)}),
          flush=True)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
