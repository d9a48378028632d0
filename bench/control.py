#!/usr/bin/env python3
"""Readings from which a cell's correctness limit is set.  Not part of the
benchmark's runs.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the cell's own run (set-up, warm-up and a
window of ``--seconds`` at the cell's load, through ``bench/cell.py``),
then over the same sample of served requests

- the program's readings: the numbers a run may compare with the cell's
  limits (``bench/cell.py::gap_number``: ``served_gap``, and
  ``share_over_<g>`` at 0.01, 0.05, 0.1 and any ``<g>`` the limits file
  names), over the gaps by which each served token's reference logit
  lies below the reference's best, and
- the control's readings: the same numbers over the gaps of the token
  that the reference computed with float8 weights (the reference module
  that the configuration's ``reference`` key names, one step below the
  stated bf16) puts first at each position.

One line per seed, then a JSON summary per number: the largest program
reading (the lower end of its limit) and the smallest control reading (the
upper end).
Needs the chip the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as bench_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        cell, jax, devs, comp = bench_cell.prepare(args.workload)
    except bench_cell.NoAccelerator as e:
        bench_cell.log(f"control: {e}")
        return 2
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        out = bench_cell.serve(cell, seed, args.seconds, False, devs, comp, t)
        _, _, prog, ctrl = bench_cell.check(cell, seed, out["requests"],
                                            fp8=True)
        rows.append({"seed": seed, "program": prog, "control": ctrl,
                     "metrics": {k: v["value"] for k, v in out["metrics"].items()}})
        print(f"control {cell.name} seed {seed}: program {prog} control "
              f"{ctrl} ({time.perf_counter() - t:.1f}s)", flush=True)
    summary = {}
    for k in rows[0]["program"]:
        lower = max(r["program"][k] for r in rows)
        upper = min(r["control"][k] for r in rows)
        summary[k] = {"lower": lower, "upper": upper,
                      "ratio": upper / lower if lower else None}
    print(json.dumps({"workload": cell.name, "readings": summary,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
