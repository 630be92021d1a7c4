"""Driver of the ``serve_open_loop`` kind of traffic: one paged ``Engine``
behind one ``Scheduler``, driven through ``Scheduler.submit`` and
``Scheduler.step`` from this one thread.

The schedule comes from ``traffic.schedule`` (same multiset of lengths
and gaps for every seed). A request is due at a time on the window's
clock; ``feed: when_due`` submits it then (open loop: lateness of the
generator is recorded, and a request is timed from when it was DUE),
``feed: as_queue_has_room`` is the backlog: everything is due at t = 0 and
the harness keeps the scheduler's queue full, so nothing is refused.

The window opens in steady state: a pre-roll of the same traffic, counted
in set-up, runs until every slot has been occupied once (backlog) or for a
stated time (arrivals). Every output token is stamped with the host clock
at the end of the beat that produced it.

``correct``: once the window has closed, ``memory_peak_bytes`` is read and
the engine is freed, the plain reference runs over the prompt and served
tokens of a sample of the finished requests (the longest among them) and
reads the widest gap by which a served token's logit lies below the
reference's best.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import time

import numpy as np

from . import common, flops, readers, reference_lm, traffic as traffic_mod
from . import trace as trace_mod


_NULL = contextlib.nullcontext()


class _Tracked:
    __slots__ = ("req", "due", "submitted", "seen", "chunks", "token_t",
                 "first_t", "done_t", "admit_t")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.submitted = None
        self.seen = 0
        self.chunks = 0
        self.token_t = []
        self.first_t = None
        self.done_t = None
        self.admit_t = None     # start of the beat that gave it a slot


def build_engine(cfg, tr, seed, registry=None):
    """The system under test: the program's model class at the
    configuration's sizes, the benchmark's seeded weights, one paged
    engine and its scheduler."""
    import jax.numpy as jnp
    from apex_tpu import serving
    from apex_tpu.models.transformer_lm import TransformerLM

    H, L, nh, S, V = reference_lm.sizes(cfg)
    model = TransformerLM(vocab_size=V, hidden=H, num_layers=L,
                          num_heads=nh, max_seq_len=S, dtype=jnp.bfloat16)
    params = reference_lm.program_tree(reference_lm.seeded_weights(cfg, seed))
    engine = serving.Engine(model, params, registry=registry,
                            **tr["engine"])
    del params
    sched = serving.Scheduler(engine, registry=registry, **tr["scheduler"])
    return engine, sched


class Loop:
    """The beat loop and everything it observes."""

    def __init__(self, engine, sched, schedule, tr, annotate=False):
        from apex_tpu import serving

        self.engine, self.sched = engine, sched
        self.Request, self.QueueFull = serving.Request, serving.QueueFull
        self.terminal = lambda r: r.status.terminal
        self.todo = [_Tracked(None, s["due"]) for s in schedule]
        self.specs = schedule
        self.next = 0
        self.live = []
        self.all = []
        self.backlog = tr["feed"] == "as_queue_has_room"
        self.chunk_len = int(tr["engine"]["chunk_len"])
        self.beats = []        # (t_start, t_end, host_s, decode_ctx, n_dec,
        #                         chunks [(offset, n, last)])
        self.refused = 0
        self.slots_used = 0
        self.t_base = None
        self.annotate = annotate

    def _span(self, name):
        if not self.annotate:
            return _NULL
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self):
        self.t_base = time.perf_counter()

    def _submit_due(self, now):
        with self._span("bench.submit"):
            while self.next < len(self.todo):
                tk = self.todo[self.next]
                if not self.backlog and tk.due > now - self.t_base:
                    break
                spec = self.specs[self.next]
                tk.req = self.Request(
                    prompt=spec["prompt"],
                    max_new_tokens=spec["max_new_tokens"], temperature=0.0)
                try:
                    self.sched.submit(tk.req)
                except self.QueueFull:
                    if self.backlog:
                        break
                    self.refused += 1
                    tk.done_t = now
                tk.submitted = time.perf_counter()
                self.next += 1
                self.all.append(tk)
                if tk.done_t is None:
                    self.live.append(tk)

    def beat(self):
        """Submit what is due, one ``Scheduler.step``, stamp what came."""
        now = time.perf_counter()
        if (not self.backlog and not self.live
                and self.next < len(self.todo)):
            # an idle server waits for its next request; it does not spin
            # on empty beats (a process that did ran every later program
            # launch some 5 ms slower, 3 of 3 - PERF.md, PR 24)
            wait = self.t_base + self.todo[self.next].due - now
            if wait > 0:
                time.sleep(wait)
                now = time.perf_counter()
        self._submit_due(now)
        with self._span("bench.step"):
            t_a = time.perf_counter()
            dw0 = self.engine.device_wait_s
            self.sched.step()
            t_b = time.perf_counter()
            host = (t_b - t_a) - (self.engine.device_wait_s - dw0)
        if not self.live and not self.backlog:
            return t_b
        with self._span("bench.observe"):
            ctx_sum, n_dec, chunks, still = 0, 0, [], []
            for tk in self.live:
                r = tk.req
                if tk.admit_t is None:
                    if r.status == "queued":
                        still.append(tk)
                        continue
                    tk.admit_t = t_a
                plen = len(r.prompt)
                if r.chunks > tk.chunks:
                    for c in range(tk.chunks, r.chunks):
                        o = c * self.chunk_len
                        n = min(self.chunk_len, plen - o)
                        chunks.append((o, n, o + n >= plen))
                    tk.chunks = r.chunks
                n_out = len(r.output_tokens)
                if n_out > tk.seen:
                    for j in range(tk.seen, n_out):
                        tk.token_t.append(t_b)
                        if j > 0:
                            ctx_sum += plen + j
                            n_dec += 1
                    if tk.first_t is None:
                        tk.first_t = t_b
                        self.slots_used += 1
                    tk.seen = n_out
                if self.terminal(r):
                    tk.done_t = t_b
                else:
                    still.append(tk)
            self.live = still
            self.beats.append((t_a, t_b, host, ctx_sum, n_dec, chunks))
        return t_b


def window_metrics(loop, t0, t1):
    """End-to-end numbers over ALL the work of [t0, t1): every output
    token that arrived, every gap that ended, every first token (or
    failure) in the window."""
    tokens, gaps, ttft, failed, attempted = 0, [], [], 0, 0
    for tk in loop.all:
        ts = tk.token_t
        tokens += sum(1 for t in ts if t0 < t <= t1)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
        due_abs = loop.t_base + tk.due
        if tk.first_t is not None and t0 < tk.first_t <= t1:
            ttft.append((tk.first_t - due_abs) * 1e3)
        bad = tk.req is None or (tk.done_t is not None
                                 and tk.req.status.value != "finished")
        if tk.done_t is not None and t0 < tk.done_t <= t1:
            attempted += 1
            if bad:
                failed += 1
                if tk.first_t is None:
                    ttft.append(float("inf"))
    return tokens, gaps, ttft, failed, attempted


def sample_finished(loop, t0, t1, n, seed):
    """Finished requests of the window: the longest, and others drawn
    from the seed."""
    done = [tk for tk in loop.all if tk.done_t is not None
            and t0 < tk.done_t <= t1 and tk.req is not None
            and tk.req.status.value == "finished" and tk.req.output_tokens]
    if not done:
        return []
    done.sort(key=lambda tk: tk.req.uid)
    longest = max(done, key=lambda tk: len(tk.req.prompt)
                  + len(tk.req.output_tokens))
    rest = [tk for tk in done if tk is not longest]
    rng = np.random.default_rng(seed)
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return [longest] + pick


def check_served(cfg, seed, sample, limits, lowp=None):
    """The reference over each sampled request's prompt and served
    tokens. Returns the numbers compared and, with ``lowp``, the
    control's reading."""
    weights = reference_lm.seeded_weights(cfg, seed)
    worst, worst_ctrl, n_tok, wrong_len = 0.0, 0.0, 0, 0
    for prompt, output, want in sample:
        if len(output) != want:
            wrong_len += 1
        served, ctrl = reference_lm.served_token_gaps(
            weights, cfg, prompt, output, lowp)
        worst = max(worst, float(served.max()))
        worst_ctrl = max(worst_ctrl, float(ctrl.max()))
        n_tok += len(output)
    del weights
    numbers = {"served_logit_gap_widest": (worst if sample else float("nan"),
                                           limits["served_logit_gap"]),
               "sampled_requests_of_wrong_length": (float(wrong_len), 0.0)}
    return numbers, worst_ctrl, n_tok


def run(cell, cfg, tr, args, bench, *, device_check=True, fault=None,
        control=None):
    """One run of a serving cell. ``fault`` (tests) breaks the timed path
    underneath; ``control`` (``checks/calibrate_serve.py``) also reads, at
    every position of the same prompts and served tokens, the gap of the
    token that the reference in that lower precision puts first. A
    benchmark run passes neither."""
    import jax

    device = common.require_chip(cell["chips"], device_check)
    common.enable_compile_cache()
    out = common.out_dir(cell["name"])
    compiles = common.CompileCounter()
    V = int(cfg["vocab_size"])
    t_s = time.perf_counter()
    schedule = traffic_mod.schedule(tr, args.seed, V)
    common.log(f"schedule of {len(schedule)} requests made in "
               f"{time.perf_counter() - t_s:.2f}s")
    engine, sched = build_engine(cfg, tr, args.seed)
    if fault is not None:
        _plant(engine, fault)
    if args.trace:
        common.log(f"program kernels: {json.dumps(engine.program_kernels())}")
    loop = Loop(engine, sched, schedule, tr, annotate=bool(args.trace))

    # ---- warm-up (set-up): one short request through both programs, so
    # that they are compiled, or read from the cache, before the arrival
    # clock starts and no request waits for a compiler
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

    # ---- the window; then, in a traced run, the traced phase. The trace
    # comes after, because stopping a trace holds this thread for seconds,
    # in which an open loop's arrivals would pile up ahead of the window
    with common.QuietGC():
        n0 = compiles.n
        live0, waiting0 = len(loop.live), sum(
            1 for tk in loop.live if tk.first_t is None)
        calls0 = _runtime_seconds(engine)
        t0 = time.perf_counter()
        setup_s = t0 - common.T_PROCESS_START
        while loop.beat() - t0 < args.seconds:
            pass
        t1 = loop.beats[-1][1]
        calls1 = _runtime_seconds(engine)
        compiles_in_window = compiles.n - n0
    live1, waiting1 = len(loop.live), sum(
        1 for tk in loop.live if tk.first_t is None)
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
    # first tokens of the window again, from the start of the beat that
    # gave the request its slot: what prefill costs, whatever the queue
    ttft_admit = [(tk.first_t - tk.admit_t) * 1e3 for tk in loop.all
                  if tk.first_t is not None and t0 < tk.first_t <= t1]
    window = t1 - t0
    used = sum(1 for tk in loop.all if t0 <= tk.submitted < t1)
    use = {"submitted_in_window": used, "left": len(loop.todo) - loop.next}
    sample = [(list(tk.req.prompt), list(tk.req.output_tokens),
               tk.req.max_new_tokens) for tk in sample_finished(
                   loop, t0, t1, int(tr["check"]["sample"]), args.seed)]
    beats = [b for b in loop.beats if t0 < b[1] <= t1]
    # what the engine's own counters say a beat of the window spent in
    # the runtime's calls: how this process differs from the next, as the
    # program saw it (a key of the line for diagnosis, never a metric)
    per_beat = {k: (calls1[k] - calls0[k]) * 1e3 / len(beats)
                for k in calls0}
    traced_beats = [b for b in loop.beats
                    if traced and traced[0] <= b[0] and b[1] <= traced[1]]
    late_ms = [(tk.submitted - loop.t_base - tk.due) * 1e3
               for tk in loop.all if not loop.backlog
               and t0 <= tk.submitted < t1]
    with open(os.path.join(out, f"beats_seed{args.seed}_trace{args.trace}"
                           ".json"), "w") as f:
        json.dump({"window_s": window, "tokens": tokens,
                   "first_tokens": len(ttft), "finished": attempted,
                   "beat_end_s": [b[1] - t0 for b in beats],
                   "beat_s": [b[1] - b[0] for b in beats],
                   "beat_host_s": [b[2] for b in beats],
                   "beat_tokens": [b[4] for b in beats],
                   "beat_context_tokens": [b[3] for b in beats],
                   "beat_chunks": [len(b[5]) for b in beats]}, f)
    bw = [b[1] - b[0] for b in beats]
    worst = int(np.argmax(bw))
    common.log(f"window {window:.3f}s: {tokens} tokens, {len(gaps)} gaps, "
               f"{len(ttft)} first tokens, {attempted} ended ({failed} "
               f"failed, {loop.refused} refused), {len(beats)} beats; "
               f"longest beat {bw[worst] * 1e3:.1f} ms (the host's part "
               f"{beats[worst][2] * 1e3:.1f} ms) at "
               f"{beats[worst][1] - t0:.2f}s (median "
               f"{np.median(bw) * 1e3:.1f} ms); submitted and not ended "
               f"{live0} as it opened ({waiting0} before their first "
               f"token), {live1} ({waiting1}) as it closed; the window "
               f"submitted {used} requests of the schedule, "
               f"{use['left']} are left")
    inf = float("inf")
    ttft_clean = [1e9 if t == inf else t for t in ttft]
    if ttft_clean:
        common.log(f"first tokens from when due: median "
                   f"{common.percentile(ttft_clean, 50):.0f} ms, p90 "
                   f"{common.percentile(ttft_clean, 90):.0f} ms over "
                   f"{len(ttft_clean)}")
    # one tail, two names: a cell below capacity reports it as
    # ``itl_p95_chat_ms`` under a bound of its own (an open loop's tail
    # swings more than a full server's); which a cell reports is
    # BENCHMARK.json's to say
    itl_p95 = common.percentile(gaps, 95) if gaps else None
    values = {"serve_tokens_per_s": tokens / window, "itl_p95_ms": itl_p95,
              "itl_p95_chat_ms": itl_p95, "setup_s": setup_s}
    events = []
    for b in beats:
        events += [("chunk",) + c for c in b[5]]
        if b[4]:
            events += [("decode", b[3] / b[4])] * b[4]
    model_flops = flops.serve_window_flops(cfg, events)

    # ---- free the program, read memory, then the reference
    engine.close()
    del engine, sched, loop.engine, loop.sched
    trk_all = loop.all
    del loop
    gc.collect()
    device["memory_peak_bytes"] = common.memory_peak_bytes()
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    numbers, ctrl_gap, n_tok = check_served(cfg, args.seed, sample,
                                            tr["check"]["limits"], control)
    if control:
        common.log(f"control {control}: widest gap of its first tokens "
                   f"{ctrl_gap:.6g} (program's served tokens: "
                   f"{numbers['served_logit_gap_widest'][0]:.6g})")
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
        ctx = {"counters": {"compiles_in_window": compiles_in_window},
               "series": {"beat_host_ms": [b[2] * 1e3 for b in beats],
                          "gen_late_ms": late_ms,
                          "ttft_ms": ttft_clean,
                          "ttft_admit_ms": ttft_admit},
               "rates": {"model_flops_per_s": model_flops / window},
               "cfg": cfg, "traffic": tr, "peaks": device["peaks"],
               "chips": cell["chips"], "trace": tr_,
               "serve": {
                   "traced_decode_context_tokens": sum(
                       b[3] for b in traced_beats),
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
                              "engine_ms_per_beat": per_beat})
    return ok


def _runtime_seconds(engine):
    return {"upload": engine.upload_s, "launch": engine.launch_s,
            "readback": engine.readback_s}


def _plant(engine, fault):
    """Test-only: break the timed path underneath the harness.
    ``token_altered``: every seventh decode step hands back other tokens
    than it computed."""
    if fault != "token_altered":
        raise ValueError(fault)
    orig, calls = engine.decode_step, [0]

    def altered(*a, **kw):
        toks = np.array(orig(*a, **kw))
        calls[0] += 1
        if calls[0] % 7 == 0:
            toks = (toks + 1) % 64
        return toks

    engine.decode_step = altered
