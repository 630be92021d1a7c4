"""Chip probes for the program's phase records (PR 25). Never a cell:
nothing here is part of the benchmark's result.

    python3 benchmarks/checks/probe_phases.py table --workload <cell> --seed N
        one traced run of a cell in this process, then the phase table
        of its traced beats (or of the window's turns): p50 and p95 of
        each phase's self time, the idle seconds by phase, and what
        ``python -m apex_tpu.telemetry summarize --trace`` says of the
        kept trace.
    python3 benchmarks/checks/probe_phases.py run --ring 0|1 -- <run.py arguments>
        one benchmark run with the flight recorder off or on.
    python3 benchmarks/checks/probe_phases.py micro
        what one ``tracing.phase`` costs on this host: ring on, ring
        off, and with a profiler session open.
    python3 benchmarks/checks/probe_phases.py fleet <out.jsonl> -- <mode and arguments> ...
        several of the above, each in a fresh process, one after the
        other (this process never touches JAX); ``---`` separates them.
        ``exec <dir> <script> <arguments>`` runs another checkout's
        script from its root (the parent commit, unpacked under a
        directory of the repo that git ignores). All share one compile
        cache. The last line of each child's standard output goes to
        ``chiprun_out/<out.jsonl>``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def _pct(vals, q):
    from benchmarks.lib import common

    return common.percentile(vals, q) if vals else None


def phase_table(ring, roots):
    """``{phase: {p50_ms, p95_ms, mean_ms, per_root}}`` of self time per
    root (a beat, a turn), and the roots' own durations."""
    from benchmarks.lib import program_spans

    ids = {r.id for r in roots}
    count = {}
    for r in ring.records(since=roots[0].t0):
        if r.root in ids:
            count[r.name] = count.get(r.name, 0) + 1
    out = {}
    for n, secs in sorted(program_spans.self_time_per_root(
            ring, roots).items()):
        v = [x * 1e3 for x in secs]
        out[n] = {"p50_ms": _pct(v, 50), "p95_ms": _pct(v, 95),
                  "mean_ms": sum(v) / len(v),
                  "per_root": count[n] / len(roots)}
    durs = [r.dur * 1e3 for r in roots]
    out["(whole)"] = {"p50_ms": _pct(durs, 50), "p95_ms": _pct(durs, 95),
                      "mean_ms": sum(durs) / len(durs), "per_root": 1.0}
    return out


def beat_timeline(ring, pairs, tr, offset, program="decode"):
    """Where the device's program sits in the beat that launched it:
    per traced beat with one launch, milliseconds from the beat's start
    of the engine's phase boundaries (host clock) and of the program's
    first and last moment on the device (trace clock less ``offset``),
    and the idle stretches of 100 us and more that begin inside the
    beat with the phase the host was in at each one's start and end.
    Medians over the beats, and the first three beats as they were."""
    from benchmarks.lib import program_spans

    dev = sorted(tr.modules)[0] if tr.modules else None
    mods = sorted((s, e) for s, e, n, _ in tr.modules.get(dev, ())
                  if program in n)
    gs, ge = program_spans._idle_gaps(tr)
    rows = []
    for _, _, b in pairs:
        recs = [r for r in ring.records(since=b.t0) if r.root == b.id]
        launches = [r for r in recs if r.name == "engine.launch"]
        if len(launches) != 1 or launches[0].args["program"] != program:
            continue
        rel = lambda t: (t - b.t0) * 1e3                      # noqa: E731
        row = {"beat_ms": b.dur * 1e3}
        for r in recs:
            if r.name.startswith("engine.") or r.name == "serve.emit":
                row[r.name + ".t0"], row[r.name + ".t1"] = rel(r.t0), \
                    rel(r.t1)
        mine = [(s, e) for s, e in mods
                if b.t0 <= s - offset <= b.t1]
        if len(mine) != 1:
            continue
        row["device.program.t0"] = rel(mine[0][0] - offset)
        row["device.program.t1"] = rel(mine[0][1] - offset)

        def where(t):
            best = None
            for r in recs + [b]:
                if r.t0 <= t <= r.t1 and (best is None
                                          or r.dur < best.dur):
                    best = r
            return best.name if best else "(between beats)"
        row["gaps"] = [
            [round(rel(a - offset), 3), round(rel(z - offset), 3),
             where(a - offset), where(z - offset)]
            for a, z in zip(gs, ge)
            if z - a >= 100e-6 and b.t0 <= a - offset <= b.t1]
        rows.append(row)
    if not rows:
        return None
    keys = [k for k in rows[0] if k != "gaps"]
    med = {k: _pct([r[k] for r in rows if k in r], 50) for k in keys}
    return {"beats": len(rows), "median_ms_from_beat_start": med,
            "first_beats": rows[:3]}


def chunk_beats(ring, since):
    """Ids of the beats since ``since`` that launched a chunk program."""
    return {r.root for r in ring.records(name="engine.launch", since=since)
            if r.args["program"] == "chunk"}


def print_table(title, table):
    print(f"[probe] {title}", file=sys.stderr)
    print(f"[probe] {'phase':<22} {'p50 ms':>10} {'p95 ms':>10} "
          f"{'mean ms':>10} {'per root':>9}", file=sys.stderr)
    for n, t in table.items():
        print(f"[probe] {n:<22} {t['p50_ms']:>10.4f} {t['p95_ms']:>10.4f} "
              f"{t['mean_ms']:>10.4f} {t['per_root']:>9.2f}",
              file=sys.stderr)


def cmd_table(a):
    os.environ["BENCH_KEEP_TRACE"] = "1"
    from apex_tpu import pyprof
    from apex_tpu.telemetry import summarize, tracing
    from benchmarks import run
    from benchmarks.lib import common, program_spans, trace as trace_mod

    if a.tiny:          # the CPU rehearsal of this probe
        from benchmarks.checks import tiny, tiny_serve
        a.workload = f"tiny.{a.tiny}"
        (tiny.run_train if a.tiny == "train" else tiny_serve.run_serve)(
            a.seed, a.seconds, 1)
    else:
        run.main(["--workload", a.workload, "--seed", str(a.seed),
                  "--seconds", str(a.seconds), "--trace", "1"])
    ring = tracing.phases
    trace_dir = os.path.join(common.OUT_DIR, a.workload, "trace")
    res = {"workload": a.workload, "seed": a.seed}
    if ring.records(name="serve.beat"):
        tr = trace_mod.load(trace_dir)
        pairs = program_spans.traced_beats(ring, tr)
        res["beats"] = len(pairs)
        res["table"] = phase_table(ring, [b for _, _, b in pairs])
        chunky = chunk_beats(ring, pairs[0][2].t0)
        plain = [b for _, _, b in pairs if b.id not in chunky]
        if plain and len(plain) < len(pairs):
            res["table_no_chunk"] = phase_table(ring, plain)
        got = program_spans.idle_by_phase({"trace": tr})
        if got:
            res["idle_s_by_phase"], res["window_s"] = got
        offset, _ = program_spans.clock_offset(pairs)
        res["timeline"] = beat_timeline(ring, pairs, tr, offset)
        print(f"[probe] timeline {json.dumps(res['timeline'])}",
              file=sys.stderr)
    else:
        roots = ring.records(name="train.turn")[-program_spans.TURNS_READ:]
        res["turns"] = len(roots)
        res["table"] = phase_table(ring, roots)
        plain = [r for r in roots if r.args["it"] % 10]
        res["table_no_log"] = phase_table(ring, plain)
    for k in ("table", "table_no_chunk", "table_no_log"):
        if k in res:
            print_table(f"{a.workload} seed {a.seed}: {k}", res[k])
    pi = summarize.phase_idle(trace_dir)
    if pi is not None:
        print(summarize.render_phase_idle(pi), file=sys.stderr)
        res["summarize_phase_idle"] = pi
    evs = [e for lane, _, e in pyprof._load_events(trace_dir)
           if e["name"].startswith("apex.")]
    res["apex_events_on_host_plane"] = len(evs)
    res["outermost_with_pc_ns"] = sum(
        1 for e in evs if "pc_ns" in e.get("args", {}))
    names = {}
    for e in evs:
        names[e["name"]] = names.get(e["name"], 0) + 1
    res["apex_event_counts"] = names
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"phase_table_{a.workload}_{a.seed}.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({"probe": "table", "workload": a.workload,
                      "seed": a.seed,
                      "apex_events": res["apex_events_on_host_plane"]}))


def cmd_run(a):
    from apex_tpu.telemetry import tracing
    from benchmarks import run

    tracing.phases.enabled = bool(a.ring)
    run.main(a.rest)


def cmd_micro(a):
    import shutil
    import tempfile

    import jax
    from apex_tpu.telemetry import tracing

    def per_phase(n=200000):
        t = time.perf_counter()
        for i in range(n // 4):
            with tracing.phase("serve.beat", tick=i):
                with tracing.phase("engine.upload"):
                    pass
                with tracing.phase("engine.launch", program="decode"):
                    pass
                with tracing.phase("engine.readback", program="decode"):
                    pass
        return (time.perf_counter() - t) / n * 1e6

    res = {"probe": "micro"}
    res["ring_on_us"] = min(per_phase() for _ in range(3))
    tracing.phases.enabled = False
    res["ring_off_us"] = min(per_phase() for _ in range(3))
    tracing.phases.enabled = True
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    res["session_open_us"] = per_phase(20000)
    jax.profiler.stop_trace()
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(res))


def cmd_fleet(a):
    """Children one after the other, each a fresh process; this one
    stays off JAX."""
    jobs, cur = [], []
    for w in a.rest:
        if w == "---":
            jobs.append(cur)
            cur = []
        else:
            cur.append(w)
    if cur:
        jobs.append(cur)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, a.out)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_compile_cache"))
    for job in jobs:
        t = time.perf_counter()
        cwd = ROOT
        cmd = [sys.executable, os.path.abspath(__file__)] + job
        if job[0] == "exec":
            cwd = os.path.join(ROOT, job[1])
            cmd = [sys.executable] + job[2:]
        p = subprocess.run(cmd, cwd=cwd, env=env, stdout=subprocess.PIPE,
                           stderr=subprocess.PIPE, text=True)
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except ValueError:
            line = {"unparsed": last[-400:]}
        rec = {"job": job, "rc": p.returncode,
               "seconds": time.perf_counter() - t, "result": line}
        for ln in p.stdout.splitlines():
            # a benchmark run under a probe: its own result line
            if ln.startswith('{"correct"'):
                rec["bench_result"] = json.loads(ln)
        if p.returncode != 0:
            rec["stderr_tail"] = p.stderr[-1500:]
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        tail = [ln for ln in p.stderr.splitlines()
                if ln.startswith("[probe]") or "window " in ln
                or "program_spans" in ln]
        print(f"== {' '.join(job)} rc={p.returncode} "
              f"{rec['seconds']:.0f}s", flush=True)
        print("\n".join(tail[-40:]), flush=True)
        print(json.dumps(line)[:1200], flush=True)


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("table")
    t.add_argument("--workload", default=None)
    t.add_argument("--tiny", choices=("serve", "train"), default=None)
    t.add_argument("--seed", type=int, required=True)
    t.add_argument("--seconds", type=float, default=45)
    r = sub.add_parser("run")
    r.add_argument("--ring", type=int, choices=(0, 1), required=True)
    r.add_argument("rest", nargs=argparse.REMAINDER)
    sub.add_parser("micro")
    f = sub.add_parser("fleet")
    f.add_argument("out")
    f.add_argument("rest", nargs=argparse.REMAINDER)
    a = ap.parse_args()
    if getattr(a, "rest", None) and a.rest[0] == "--":
        a.rest = a.rest[1:]
    {"table": cmd_table, "run": cmd_run, "micro": cmd_micro,
     "fleet": cmd_fleet}[a.cmd](a)


if __name__ == "__main__":
    main()
