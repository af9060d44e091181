"""Runs sets of one cell and prints each metric's spread, the way a
bound is set: ``--sets`` sets of ``--runs`` runs, the same seeds in every
set, every run a fresh process of the benchmark's own command.

    python3 benchmark/proof.py --workload <cell> [--seconds N] [--runs 6] [--sets 2] [--trace 0]

Started on the machine that holds the chip (``chiprun -- python3
benchmark/proof.py ...``). This parent never touches JAX, so each child has
the chip to itself. Every result line is also appended to
``chiprun_out/proof-<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT

from benchmark import stats  # noqa: E402

SEEDS = (2147483659, 7, 1234567891, 42, 3000000019, 99991, 5, 2718281828)


def main():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, default=manifest["run_seconds"])
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"proof-{args.workload}.jsonl")
    sets = []
    for s in range(args.sets):
        rows = []
        for seed in SEEDS[:args.runs]:
            t0 = time.time()
            done = subprocess.run(
                manifest["command"] + [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace",
                    str(args.trace)],
                cwd=ROOT, capture_output=True, text=True, timeout=900)
            wall = time.time() - t0
            if done.returncode != 0:
                print(f"set {s} seed {seed}: rc {done.returncode}\n"
                      f"{done.stderr[-1500:]}", flush=True)
                continue
            line = json.loads(done.stdout.strip().splitlines()[-1])
            line.update(set=s, seed=seed, wall_s=round(wall, 1),
                        seconds=args.seconds)
            with open(log, "a") as f:
                f.write(json.dumps(line) + "\n")
            rows.append(line)
            print(f"set {s} seed {seed} wall {wall:.0f}s correct "
                  f"{line['correct']} attempted {line['attempted']} " + " ".join(
                      f"{k}={v['value']:.5g}"
                      for k, v in line["metrics"].items()), flush=True)
        sets.append(rows)
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    for name in names:
        per_set = [[r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]] for rows in sets]
        # each side's first run compiles: its set-up is recorded apart
        if name == "setup_s":
            per_set = [v[1:] if i == 0 else v for i, v in enumerate(per_set)]
        usable = [v for v in per_set if len(v) >= 2]
        spreads = [stats.spread(v) for v in usable]
        medians = [statistics.median(v) for v in usable]
        print(f"{name}: medians {[round(m, 5) for m in medians]} spreads "
              f"{[round(x, 5) for x in spreads]} widest "
              f"{max(spreads, default=float('nan')):.5f} -> bound ~ "
              f"{5 * max(spreads, default=float('nan')):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
