"""Driver of the ``serve_model`` kind of traffic (PR 34): one paged
``Engine`` behind one ``Scheduler`` serving whatever architecture the
program's ``build_lm`` builds from the configuration's file - the kind a
NEW architecture's cell names, so that it brings files and no copy of this
orchestration.

What an architecture brings, by name in its traffic file (``model``):

- ``reference``: a module under ``benchmarks/lib/`` with
  ``seeded_weights(cfg, seed)``, ``program_tree(weights)`` and
  ``served_token_gaps(weights, cfg, prompt, output, lowp, pad_to=,
  reach=)`` - the plain float32 reference and the benchmark's own weights;
- ``work``: a module with ``serve_window_flops(cfg, events, routed=)`` (the
  operations THIS chip does for the window's tokens; ``routed`` the
  window's ``[layers, experts]`` counter of tokens routed, or None) and
  the work functions its per-layer metrics' files name;
- ``check.rule``: which reading of the served tokens' logit gaps decides
  ``correct`` - ``widest_gap`` (``served_logit_gap_widest`` under
  ``check.limits.served_logit_gap``: the GPT-2 cells' rule) or
  ``off_best_share`` (``served_tokens_off_best_share``, the part of the
  served tokens further than ``check.token_gap`` below the reference's
  best, under ``check.limits.off_best_share``: the ``serve_zaya`` cell's
  rule, for a model where a router tie changes a layer's output wholly).
  Both readings are printed either way.

The beat loop, the window's numbers, the sample, the schedule and the
trace reduction are ``serve.py``'s, ``traffic.py``'s and ``trace.py``'s,
by import. The program is asked to BUILD the model before any weight is
drawn: a program that cannot (an older commit, an unknown ``model_type``)
ends at once with a message and a non-zero exit code.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import time

import numpy as np

from . import common, readers, traffic as traffic_mod
from . import trace as trace_mod
from .serve import (Loop, _runtime_seconds, sample_finished,
                    window_metrics)

RULES = {"widest_gap": ("served_logit_gap_widest", "served_logit_gap"),
         "off_best_share": ("served_tokens_off_best_share",
                            "off_best_share")}


def _module(name):
    return importlib.import_module(f"{__package__}.{name}")


def build_model(cell, cfg):
    """The program's model for the configuration, or the end of the run:
    asked before anything is drawn or placed on the device."""
    try:
        import jax.numpy as jnp
        from apex_tpu.models import build_lm
        return build_lm(cfg, dtype=jnp.bfloat16)
    except (ImportError, ValueError, NotImplementedError, KeyError,
            TypeError) as e:
        raise SystemExit(f"benchmark: this program cannot build the "
                         f"configuration {cell['config']!r}: "
                         f"{type(e).__name__}: {e}")


def check_served(ref, cfg, weights, sample, check, lowp=None, pad_to=None):
    """The reference over each sampled request's prompt and served
    tokens. Returns the numbers compared, the control's readings (with
    ``lowp``: its widest gap and its off-best share), the number of
    served tokens read and the per-token arrays (gaps, control gaps, tie
    margins)."""
    gaps, ctrl, ties, wrong_len = [], [], [], 0
    for prompt, output, want in sample:
        if len(output) != want:
            wrong_len += 1
        g, c, t = ref.served_token_gaps(
            weights, cfg, prompt, output, lowp, pad_to=pad_to,
            reach=int(check.get("tie_reach", 0)))
        gaps.append(g)
        ctrl.append(c)
        ties.append(t)
    cat = lambda v: np.concatenate(v) if v else np.zeros((0,))  # noqa: E731
    gaps, ctrl, ties = cat(gaps), cat(ctrl), cat(ties)
    tg = float(check["token_gap"])
    read = lambda g: {                                          # noqa: E731
        "widest_gap": float(g.max()) if len(g) else float("nan"),
        "off_best_share": float(np.mean(g > tg)) if len(g)
        else float("nan")}
    ours, theirs = read(gaps), read(ctrl)
    name, limit = RULES[check["rule"]]
    numbers = {name: (ours[check["rule"]], check["limits"][limit]),
               "sampled_requests_of_wrong_length": (float(wrong_len), 0.0)}
    return numbers, (ours, theirs), len(gaps), (gaps, ctrl, ties)


def _expert_counts(engine):
    """The program's ``[layers, experts]`` counter of tokens routed, or
    None where it has none."""
    read = getattr(engine, "moe_tokens_per_expert", None)
    got = read() if read is not None else None
    return None if got is None else np.asarray(got, np.int64)


def _plant(engine, fault, vocab):
    """Test-only: break the timed path underneath the harness.
    ``token_altered``: every third decode step hands back other tokens
    than it computed."""
    if fault != "token_altered":
        raise ValueError(fault)
    orig, calls = engine.decode_reconcile, [0]

    def altered(*a, **kw):
        toks, finite, step_s = orig(*a, **kw)
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = (np.array(toks) + 1) % vocab
        return toks, finite, step_s

    engine.decode_reconcile = altered


def run(cell, cfg, tr, args, bench, *, device_check=True, control=None,
        fault=None):
    """One run of the cell. ``control`` (the calibration script, the
    checks) also reads, over the same prompts and served tokens, the gap
    of the token that the reference in that lower precision puts first;
    ``fault`` (the checks) breaks the timed path underneath. A benchmark
    run passes neither."""
    import jax

    device = common.require_chip(cell["chips"], device_check)
    model = build_model(cell, cfg)
    ref, work = _module(tr["model"]["reference"]), _module(tr["model"]["work"])
    common.enable_compile_cache()
    out = common.out_dir(cell["name"])
    compiles = common.CompileCounter()
    V = int(cfg["vocab_size"])
    t_s = time.perf_counter()
    schedule = traffic_mod.schedule(tr, args.seed, V)
    common.log(f"schedule of {len(schedule)} requests made in "
               f"{time.perf_counter() - t_s:.2f}s")
    from apex_tpu import serving
    from apex_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    t_s = time.perf_counter()
    weights = ref.seeded_weights(cfg, args.seed)
    jax.block_until_ready(weights)
    common.log(f"weights made in {time.perf_counter() - t_s:.1f}s")
    engine = serving.Engine(model, ref.program_tree(weights),
                            registry=registry, **tr["engine"])
    sched = serving.Scheduler(engine, registry=registry, **tr["scheduler"])
    if fault is not None:
        _plant(engine, fault, V)
    if args.trace:
        common.log(f"program kernels: {json.dumps(engine.program_kernels())}")
        common.log(f"program memory: {json.dumps(engine.program_memory())}")
    loop = Loop(engine, sched, schedule, tr, annotate=bool(args.trace))

    # ---- warm-up (set-up): one short request through both programs
    t_w = time.perf_counter()
    warm = loop.Request(prompt=[1] * 16, max_new_tokens=2, temperature=0.0)
    sched.submit(warm)
    while not warm.status.terminal:
        sched.step()
    if warm.status.value != "finished":
        raise SystemExit("benchmark: the warm-up request did not finish; "
                         "nothing measured")
    common.log(f"programs ready {time.perf_counter() - t_w:.1f}s after the "
               f"engine, {time.perf_counter() - common.T_PROCESS_START:.1f}s "
               "after the process started")

    # ---- pre-roll (set-up): the same traffic until steady state
    loop.start()
    pre = tr["preroll"]
    while True:
        t = loop.beat()
        if pre["until"] == "slots_used":
            if loop.slots_used >= int(tr["engine"]["slots"]):
                break
        elif t - loop.t_base >= float(pre["seconds"]):
            break
        if any(tk.done_t is not None and tk.req.status.value != "finished"
               for tk in loop.all):
            raise SystemExit("benchmark: a request failed or was refused "
                             "in the pre-roll; nothing measured")
        if t - loop.t_base > 240:
            raise SystemExit("benchmark: pre-roll did not reach steady "
                             "state in 240 s")
    common.log(f"pre-roll {time.perf_counter() - loop.t_base:.1f}s, "
               f"{len(loop.beats)} beats, {loop.next} submitted, "
               f"{len(loop.live)} live")

    # ---- the window, then (traced run) the traced phase; the expert
    # counter is read outside both clocks
    counts0 = _expert_counts(engine)
    with common.QuietGC():
        n0 = compiles.n
        calls0 = _runtime_seconds(engine)
        t0 = time.perf_counter()
        setup_s = t0 - common.T_PROCESS_START
        while loop.beat() - t0 < args.seconds:
            pass
        t1 = loop.beats[-1][1]
        calls1 = _runtime_seconds(engine)
        compiles_in_window = compiles.n - n0
    counts1 = _expert_counts(engine)
    in_window = common.memory_in_use_bytes()
    traced = None
    trace_dir = os.path.join(out, "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            ta = time.perf_counter()
            while loop.beat() - ta < float(tr["trace_seconds"]):
                pass
        traced = (ta, time.perf_counter())
        jax.profiler.stop_trace()
    if loop.next >= len(loop.todo):
        raise SystemExit("benchmark: the schedule ran out inside the "
                         "window; raise 'blocks' in the traffic file")

    tokens, gaps, ttft, failed, attempted = window_metrics(loop, t0, t1)
    ttft_admit = [(tk.first_t - tk.admit_t) * 1e3 for tk in loop.all
                  if tk.first_t is not None and t0 < tk.first_t <= t1]
    window = t1 - t0
    used = sum(1 for tk in loop.all if t0 <= tk.submitted < t1)
    use = {"submitted_in_window": used, "left": len(loop.todo) - loop.next}
    sample = [(list(tk.req.prompt), list(tk.req.output_tokens),
               tk.req.max_new_tokens) for tk in sample_finished(
                   loop, t0, t1, int(tr["check"]["sample"]), args.seed)]
    beats = [b for b in loop.beats if t0 < b[1] <= t1]
    per_beat = {k: (calls1[k] - calls0[k]) * 1e3 / len(beats)
                for k in calls0}
    traced_beats = [b for b in loop.beats
                    if traced and traced[0] <= b[0] and b[1] <= traced[1]]
    bw = [b[1] - b[0] for b in beats]
    itl_p95 = common.percentile(gaps, 95) if gaps else float("nan")
    common.log(f"window {window:.3f}s: {tokens} tokens, {len(gaps)} gaps "
               f"(p95 {itl_p95:.1f} ms), {len(ttft)} first tokens, "
               f"{attempted} ended ({failed} failed, {loop.refused} "
               f"refused), {len(beats)} beats (median "
               f"{np.median(bw) * 1e3:.1f} ms, the host's part "
               f"{np.median([b[2] for b in beats]) * 1e3:.2f} ms, longest "
               f"{max(bw) * 1e3:.1f} ms), {np.mean([b[4] for b in beats]):.1f}"
               f" slots decoding a beat, "
               f"{sum(len(b[5]) for b in beats)} chunks; the window "
               f"submitted {used} requests of the schedule, {use['left']} "
               "are left")
    values = {"serve_tokens_per_s": tokens / window, "setup_s": setup_s}
    events = []
    for b in beats:
        events += [("chunk",) + c for c in b[5]]
        if b[4]:
            events += [("decode", b[3] / b[4])] * b[4]
    routed = None
    if counts0 is not None:
        routed = (counts1 - counts0).tolist()
        per_layer = np.asarray(routed).sum(1)
        common.log(f"tokens routed in the window: {int(per_layer[0])} a "
                   f"layer; least and most to one expert of a layer "
                   f"{int(np.min(routed))} and {int(np.max(routed))}")
    model_flops = work.serve_window_flops(cfg, events, routed=routed)
    gauges = {k: v for k, v in registry.gauges.items()
              if k.startswith(("serving.kv.", "serving.state.",
                               "serving.moe."))}

    # ---- free the program, read memory, then the reference
    engine.close()
    del engine, sched, loop.engine, loop.sched, model
    trk_all = loop.all
    del loop
    gc.collect()
    device["memory_peak_bytes"] = common.memory_peak_bytes(in_window)
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    check = tr["check"]
    numbers, (ours, theirs), n_tok, arrays = check_served(
        ref, cfg, weights, sample, check, control,
        pad_to=int(tr["max_total"]))
    del weights
    g, c, ties = arrays
    if len(g):
        delta = float(check.get("tie_margin", 0.0))
        q = lambda p: float(np.percentile(g, p))                # noqa: E731
        common.log(f"served tokens read: {n_tok}; not the reference's "
                   f"first choice {int((g > 0).sum())}; gap p95 {q(95):.4g} "
                   f"p99 {q(99):.4g} widest {q(100):.4g}; further than "
                   f"{check['token_gap']:g} below the best "
                   f"{ours['off_best_share']:.6g} of them; on a tie (margin "
                   f"under {delta:g} within {check.get('tie_reach', 0)} "
                   f"positions) {float((ties < delta).mean()):.3f} of them, "
                   f"the widest gap off ties "
                   f"{float(g[ties >= delta].max(initial=0.0)):.4g}")
    dump = os.environ.get("BENCH_CHECK_DUMP")
    if dump:
        os.makedirs(os.path.dirname(dump) or ".", exist_ok=True)
        np.savez(f"{dump}_seed{args.seed}.npz", gaps=g, ctrl=c, ties=ties)
    if control:
        common.log(f"control {control}: its first tokens' widest gap "
                   f"{theirs['widest_gap']:.6g}, share off the reference's "
                   f"best {theirs['off_best_share']:.6g} (program's served "
                   f"tokens: {ours['widest_gap']:.6g}, "
                   f"{ours['off_best_share']:.6g})")
    numbers["failed_or_refused"] = (float(failed + sum(
        1 for tk in trk_all if tk.req is None)), 0.0)
    numbers["sample_min_requests"] = (-float(len(sample)), -1.0)
    compared, ok = common.compare(numbers)
    common.log(f"reference over {len(sample)} requests, {n_tok} served "
               f"tokens, took {time.perf_counter() - t_ref:.1f}s")

    extra = breakdown = None
    if args.trace:
        tr_ = trace_mod.load(trace_dir)
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"counters": {"compiles_in_window": compiles_in_window,
                            "moe_tokens_per_expert": routed},
               "series": {"beat_host_ms": [b[2] * 1e3 for b in beats],
                          "ttft_admit_ms": ttft_admit},
               "rates": {"model_flops_per_s": model_flops / window},
               "cfg": cfg, "traffic": tr, "peaks": device["peaks"],
               "chips": cell["chips"], "trace": tr_,
               "serve": {
                   "traced_decode_context_tokens": sum(
                       b[3] for b in traced_beats),
                   "traced_decode_tokens": [b[4] for b in traced_beats],
                   "traced_chunks": [c[:2] for b in traced_beats
                                     for c in b[5]]}}
        values = readers.read_all(bench, cell["name"], ctx)
        extra = {"busy_s": tr_.busy_s(), "window_s": tr_.window_s}
        breakdown = tr_.breakdown(default_host="bench.loop")
        common.log(f"programs in trace: {tr_.module_names()[:12]}")
    common.emit_result(bench=bench, cell=cell["name"], trace=args.trace,
                       correct=ok, attempted=attempted, failed=failed,
                       values=values, device=device, compared=compared,
                       extra_device=extra, breakdown=breakdown,
                       extra={"schedule": use,
                              "engine_ms_per_beat": per_beat,
                              "token_gap_p95_ms": itl_p95,
                              "gauges": gauges,
                              "check_readings": {"program": ours,
                                                 "control": theirs
                                                 if control else None}})
    return ok
