"""Chip probe: the sets of runs that a bound is set from (PR 29). Never a
cell; it only calls the benchmark's one command, each run a fresh
process, and this process stays off JAX, so every child finds the chip
free.

    python3 benchmarks/checks/run_sets.py <out.jsonl> --workload <cell> [--seeds 8] [--sets 2] [--seed N] [--seconds 45] [--trace 0]
    python3 benchmarks/checks/run_sets.py <out.jsonl> --workload <cell> --seed-list a,b,a,b [--seconds 8]

``--sets`` sets of ``--seeds`` runs, the same seeds in every set (or one
set of the seeds of ``--seed-list``, in that order). Every result line
goes to ``chiprun_out/<out.jsonl>`` with its set, seed and wall time.
Then, for each end-to-end metric and each set: the median; the spread as
the contract reads it (distance between the quartiles of
``statistics.quantiles(v, n=4)`` over the median); the same with the run
farthest from the median left out, as the driver's check of tightness
reads it; and the range without that run, as ISSUE 29 read it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
OUT = os.path.join(ROOT, "chiprun_out")


def run_child(cmd, out_name, **tags):
    """Run ``cmd`` from the checkout's root. Returns its last line as an
    object (``{"unparsed": ...}`` where it is none), with ``rc``,
    ``wall_s`` and ``tags`` added, after appending it to
    ``chiprun_out/<out_name>``."""
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE, text=True)
    last = (p.stdout.strip().splitlines() or [""])[-1]
    try:
        line = json.loads(last)
    except ValueError:
        line = {"unparsed": last[-400:]}
    if p.returncode != 0 or "unparsed" in line or line.get("correct") is False:
        line["stderr"] = p.stderr[-3000:]
    line.update(tags, rc=p.returncode, wall_s=time.perf_counter() - t)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, out_name), "a") as f:
        f.write(json.dumps(line) + "\n")
    return line


def iqr_share(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values):
    med = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - med))[:-1]


def spreads(values):
    """(median, quartile spread, the same less the farthest run, range
    less the farthest run), the three spreads as shares of the median."""
    med = statistics.median(values)
    kept = without_farthest(values)
    return (med, iqr_share(values),
            iqr_share(kept) * statistics.median(kept) / med,
            (max(kept) - min(kept)) / med)


def summarize(lines, log=print):
    """``lines``: result lines with ``set`` and ``metrics``."""
    names = sorted({m for ln in lines for m in ln["metrics"]})
    for name in names:
        for k in sorted({ln["set"] for ln in lines}):
            v = [ln["metrics"][name]["value"] for ln in lines
                 if ln["set"] == k and name in ln["metrics"]]
            if len(v) < 4 or statistics.median(v) == 0:
                continue        # too few runs, or a count that reads 0
            med, iqr, less_one, rng = spreads(v)
            log(f"{name} set {k}: median {med:.6g}, spread "
                f"{100 * iqr:.3f}%, without the farthest run "
                f"{100 * less_one:.3f}% (range {100 * rng:.3f}%), "
                f"all {[round(x, 4) for x in v]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2900001001)
    ap.add_argument("--seed-list", default=None)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    if a.seed_list:
        plan = [(0, int(s)) for s in a.seed_list.split(",")]
    else:
        plan = [(k, a.seed + i) for k in range(a.sets)
                for i in range(a.seeds)]
    lines = []
    for k, seed in plan:
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               "--workload", a.workload, "--seed", str(seed),
               "--seconds", str(a.seconds), "--trace", str(a.trace)]
        line = run_child(cmd, a.out, set=k, seed=seed, workload=a.workload,
                         trace=a.trace)
        if "metrics" in line:
            lines.append(line)
        mets = {n: round(m["value"], 4)
                for n, m in line.get("metrics", {}).items()}
        print(f"set {k} seed {seed} rc={line['rc']} correct="
              f"{line.get('correct')} {mets} engine="
              f"{json.dumps(line.get('engine_ms_per_beat'))} schedule="
              f"{json.dumps(line.get('schedule'))} {line['wall_s']:.0f}s",
              flush=True)
    summarize(lines)


if __name__ == "__main__":
    main()
