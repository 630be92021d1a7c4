"""Driver of the ``train_recipe`` kind of traffic: ONE call of a training
recipe's ``main(argv, on_step=hook)``; the hook marks the window.

Set-up writes a Zipf token stream made from ``--seed`` and passes it as
``--data`` (what a trainer with a corpus runs). The hook lets the warm
steps pass, fences, starts the clock; it adds no fence of its own per
step (it waits on the loss of the step two before the one just
dispatched, which costs the device nothing); at the first step dispatched
after ``--seconds`` it fences on that step's loss, stops the clock and
ends the recipe's loop by raising through it.

The same ``main()`` call's first three steps feed ``correct``: their
losses, the first gradient as the optimizer got it (from Adam's first
moment after step 1) and the parameters after step 3 are taken from the
recipe's own state while it runs, and compared, once the window has
closed and the state is freed, with the plain reference following the
same three batches from the same seed.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

from . import common, readers, reference_lm, trace as trace_mod


class _WindowClosed(Exception):
    pass


def write_token_stream(path, seed, n_tokens, vocab, offset=10):
    """Zipf-ish unigram stream, rank r with weight 1/(r + offset): numpy,
    from the seed, int32, one flat .npy."""
    rng = np.random.default_rng(seed)
    w = 1.0 / (np.arange(vocab, dtype=np.float64) + offset)
    cdf = np.cumsum(w / w.sum())
    toks = np.searchsorted(cdf, rng.random(n_tokens), side="right")
    np.save(path, np.minimum(toks, vocab - 1).astype(np.int32))


class Watchdog(threading.Thread):
    """Diagnosis only (``BENCH_WATCHDOG=1``, never in a measured run): a
    thread that sleeps 5 ms at a time and notes every wake-up that came
    50 ms late or more. A long turn of the recipe's loop with no late
    wake-up in it was one thread waiting; with one, the process stood
    still."""

    def __init__(self):
        super().__init__(daemon=True)
        self.late, self.stop = [], False

    def run(self):
        while not self.stop:
            t = time.perf_counter()
            time.sleep(5e-3)
            dt = time.perf_counter() - t
            if dt >= 50e-3:
                self.late.append((t, dt))


def _leaf_name(path):
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def _named_leaves(tree):
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_leaf_name(p), x) for p, x in flat]


class Hook:
    """The window's state machine, called by the recipe after every
    dispatch: warm -> [traced ->] measured -> closed."""

    def __init__(self, *, seconds, warm_steps, check_steps, trace_dir,
                 trace_seconds):
        self.seconds = seconds
        self.warm_steps = warm_steps
        self.check_steps = check_steps
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.phase = "warm"
        self.first_it = None
        self.check = {"losses": [], "batches": [], "found_inf": []}
        self.pending = {}          # it -> loss array not yet waited on
        self.done = []             # (it, seconds since t0) finished steps
        self.calls = []            # (entered, left) the hook, seconds since t0
        self.t0 = self.t1 = None
        self.it0 = self.it1 = None
        self.traced = None         # (t_start, t_end) host clock
        self.compiles = common.CompileCounter()
        self.compiles_in_window = None
        self._gc = None
        self._ann = None
        self.program_temp_bytes = 0   # the step's temporaries, compiler's count
        self.live_bytes = 0           # live arrays, read as the window opens

    # -- what the first steps leave for the comparison
    def _capture(self, k, frame):
        import jax
        import jax.numpy as jnp

        state = frame.f_locals["state"]
        batch = np.asarray(frame.f_locals["batch"])
        self.check["batches"].append(batch.reshape(-1, batch.shape[-1]))
        if k == 0:
            compiled = frame.f_locals.get("compiled")
            if compiled is not None:
                ma = compiled.memory_analysis()
                self.program_temp_bytes = int(
                    getattr(ma, "temp_size_in_bytes", 0) or 0)
            named = _named_leaves(state.opt_state.m)
            norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32)))) for x in xs])(
                    [x for _, x in named])
            self.check["m1"] = list(zip([n for n, _ in named], norms))
        if k == self.check_steps - 1:
            masters = state.master_params \
                if state.master_params is not None else state.params
            self.check["params"] = {
                n: np.asarray(x, np.float32) for n, x in
                _named_leaves(jax.device_get(masters))}
            self.check["m1"] = [(n, float(x)) for n, x in self.check["m1"]]

    def _open_window(self, it, metrics):
        metrics["loss"].block_until_ready()
        self.pending.clear()
        self.live_bytes = common.memory_in_use_bytes()
        self._gc = common.QuietGC().__enter__()
        self.n_compiles0 = self.compiles.n
        self.it0 = it
        self.t0 = time.perf_counter()

    def __call__(self, it, metrics):
        now = time.perf_counter()
        if self.first_it is None:
            self.first_it = it
        k = it - self.first_it
        if k < self.check_steps:
            self.check["losses"].append(metrics["loss"])
            self.check["found_inf"].append(metrics["found_inf"])
            self._capture(k, sys._getframe(1))
            return
        if self.phase == "warm":
            if k < self.warm_steps:
                return
            if self.trace_dir is not None:
                import jax

                metrics["loss"].block_until_ready()
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
                self.phase = "traced"
                self.traced = [time.perf_counter(), None]
                self.traced_it0 = it
                return
            self._open_window(it, metrics)
            self.phase = "measured"
            return
        if self.phase == "traced":
            if now - self.traced[0] < self.trace_seconds:
                return
            import jax

            metrics["loss"].block_until_ready()
            self._ann.__exit__(None, None, None)
            self.traced[1] = time.perf_counter()
            self.traced_steps = it - self.traced_it0
            jax.profiler.stop_trace()
            self._open_window(it, metrics)
            self.phase = "measured"
            return
        # measured
        self.calls.append([now - self.t0, None])
        self.pending[it] = metrics["loss"]
        if now - self.t0 >= self.seconds:
            for j in sorted(self.pending):
                self.pending[j].block_until_ready()
                self.done.append((j, time.perf_counter() - self.t0))
            self.t1 = time.perf_counter()
            self.calls[-1][1] = self.t1 - self.t0
            self.it1 = it
            self.compiles_in_window = self.compiles.n - self.n_compiles0
            self._gc.__exit__(None, None, None)
            self.phase = "closed"
            raise _WindowClosed()
        old = self.pending.pop(it - 2, None)
        if old is not None:
            old.block_until_ready()
            self.done.append((it - 2, time.perf_counter() - self.t0))
        self.calls[-1][1] = time.perf_counter() - self.t0


def _rel_gap(prog, ref, floor, names, what):
    """Worst leaf of |prog - ref| over max(ref, floor)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    i = int(np.argmax(gap))
    common.log(f"{what}: worst leaf {names[i]} program {prog[i]:.6g} "
               f"reference {ref[i]:.6g} (median leaf {floor:.6g}); median "
               f"gap {np.median(gap):.3g}")
    return float(gap[i])


def compare_training(ref, prog, names, limits):
    """The numbers ``correct`` rests on, each beside its limit.
    ``ref``/``prog``: ``losses``, ``grad_norms``, ``change_norms`` in
    ``names`` order. A gap is the distance between the program's norm and
    the reference's, over the reference's norm of that leaf or of the
    median leaf, whichever is larger; the worst leaf counts.

    A number whose limit the traffic file leaves out is read and logged
    but not compared: the losses' gaps are such (PERF.md: the float8
    control reads them as low as sound runs do, so no limit could hold)."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        if "loss_gap" in limits:
            out[f"loss_step{i}_gap"] = (abs(a - b), limits["loss_gap"])
        else:
            common.log(f"loss step {i}: program {a:.6f} reference {b:.6f} "
                       f"gap {abs(a - b):.3g} (read, not compared)")
    out["grad_norm_worst_leaf_gap"] = (
        _rel_gap(prog["grad_norms"], ref["grad_norms"],
                 float(np.median(ref["grad_norms"])), names,
                 "first gradient"), limits["grad_norm_gap"])
    out["param_change_worst_leaf_gap"] = (
        _rel_gap(prog["change_norms"], ref["change_norms"],
                 float(np.median(ref["change_norms"])), names,
                 "parameter change"), limits["param_change_gap"])
    return out


def program_readings(check, cfg, beta1=0.9):
    """From what the hook captured: the program's losses, its first
    gradient's norms (Adam's first moment after one step over 1 - beta1)
    per leaf in the reference's order, and its parameters after the
    check's steps, stacked in the reference's layout."""
    names = reference_lm.leaf_names(cfg)
    m1 = dict(check["m1"])
    missing = [n for n in names if n not in m1 or n not in check["params"]]
    if missing:
        raise RuntimeError(f"program state lacks leaves {missing[:4]}...")
    final = reference_lm.stack_named(check["params"], cfg)
    return {"losses": [float(x) for x in check["losses"]],
            "grad_norms": [m1[n] / (1.0 - beta1) for n in names]}, \
        final, names


def run(cell, cfg, traffic, args, bench, *, device_check=True, fault=None):
    import jax

    device = common.require_chip(cell["chips"], device_check)
    common.enable_compile_cache()
    out = common.out_dir(cell["name"])
    seed_recipe = args.seed % (2**31 - 1)
    data_path = os.path.join(out, "tokens.npy")
    write_token_stream(data_path, args.seed, int(traffic["data"]["tokens"]),
                       int(cfg["vocab_size"]),
                       int(traffic["data"].get("zipf_offset", 10)))
    fmt = {"data": data_path, "seed": str(seed_recipe), "iters": "1000000",
           "vocab_size": str(cfg["vocab_size"]),
           "recipe_size": str(cfg.get("recipe_size", ""))}
    argv = [a.format(**fmt) for a in traffic["argv"]]
    mod, fn = traffic["entry"].split(":")
    main = getattr(importlib.import_module(mod), fn)
    trace_dir = os.path.join(out, "trace") if args.trace else None
    hook = Hook(seconds=args.seconds, warm_steps=int(traffic["warm_steps"]),
                check_steps=int(traffic["check"]["steps"]),
                trace_dir=trace_dir,
                trace_seconds=float(traffic.get("trace_seconds", 5)))
    undo = _plant(mod, fault) if fault is not None else None
    dog = Watchdog() if os.environ.get("BENCH_WATCHDOG") else None
    if dog is not None:
        dog.start()
    common.log(f"recipe argv: {' '.join(argv)}")
    try:
        main(argv, on_step=hook)
        raise SystemExit("benchmark: the recipe ended before the window "
                         "closed; --iters too small")
    except _WindowClosed:
        pass
    finally:
        if undo is not None:
            undo()
    setup_s = hook.t0 - common.T_PROCESS_START
    if hook.traced:
        # the traced phase and the stop of the trace are not set-up
        setup_s = hook.traced[0] - common.T_PROCESS_START
    # -- the program's state is gone with main()'s frame; now read memory
    sys.last_traceback = None
    gc.collect()
    device["memory_peak_bytes"] = common.memory_peak_bytes(
        hook.live_bytes, hook.program_temp_bytes)
    common.log(f"memory: live arrays in the window {hook.live_bytes}, the "
               f"step's temporaries {hook.program_temp_bytes} (compiler), "
               f"reported peak {device['memory_peak_bytes']}")

    n_steps = hook.it1 - hook.it0
    window = hook.t1 - hook.t0
    items = n_steps * int(traffic["items_per_step"])
    rate = items / window / cell["chips"]
    # a step's time is read when the host next comes back to the hook, a
    # step or two after it finished: the stamps lag, their gaps do not
    ts = [t for _, t in hook.done]
    gaps = np.diff(ts)
    worst = int(np.argmax(gaps))
    with open(os.path.join(out, f"steps_seed{args.seed}_trace{args.trace}"
                           ".json"), "w") as f:
        json.dump({"window_s": window, "steps": n_steps,
                   "finished_at_s": ts,
                   "hook_entered_at_s": [c[0] for c in hook.calls],
                   "hook_left_at_s": [c[1] for c in hook.calls]}, f)
    common.log(f"window {window:.3f}s, {n_steps} steps, "
               f"{rate:.1f} {traffic['items']}/s/chip; longest gap between "
               f"finished steps {gaps[worst] * 1e3:.1f} ms at "
               f"{ts[worst + 1]:.2f}s of {window:.1f}s (median "
               f"{np.median(gaps) * 1e3:.1f} ms, "
               f"{int(np.sum(gaps > 1.5 * np.median(gaps)))} over 1.5x)")
    if dog is not None:
        dog.stop = True
        late = [(t - hook.t0, dt) for t, dt in dog.late
                if hook.t0 <= t <= hook.t1]
        common.log("watchdog: wake-ups 50 ms late or more in the window: "
                   + (", ".join(f"{dt * 1e3:.0f} ms at {t:.2f}s"
                                for t, dt in late) or "none"))
    # whose wait the longest iteration was: the hook's, on the loss of the
    # step two back, or the recipe's own loop (batch draw and dispatch)
    turns = [(b[0] - a[0], a[1] - a[0], a[0])
             for a, b in zip(hook.calls, hook.calls[1:])]
    if turns:
        whole, inside, at = max(turns)
        common.log(f"longest turn of the recipe's loop {whole * 1e3:.1f} ms "
                   f"at {at:.2f}s: {inside * 1e3:.1f} ms in the hook's wait, "
                   f"{(whole - inside) * 1e3:.1f} ms in the recipe")

    # -- correct: the reference follows the first steps, state now freed
    t_ref = time.perf_counter()
    prog, final, names = program_readings(hook.check, cfg)
    hook.check.pop("params")
    ref = reference_lm.train_steps(
        cfg, reference_lm.recipe_init(cfg, seed_recipe),
        hook.check["batches"], lr=float(traffic["lr"]),
        weight_decay=float(traffic["weight_decay"]),
        rows_per_block=int(traffic["check"].get("rows_per_block", 4)),
        program_final=final)
    prog["change_norms"] = ref["program_change_norms"]
    rows = np.concatenate(hook.check["batches"])
    n_same = len(rows) - len({r.tobytes() for r in rows})
    skipped = int(sum(bool(x) for x in hook.check["found_inf"]))
    numbers = compare_training(ref, prog, names, traffic["check"]["limits"])
    numbers["rows_repeated"] = (float(n_same), 0.0)
    numbers["steps_skipped_by_scaler"] = (float(skipped), 0.0)
    compared, ok = common.compare(numbers)
    common.log(f"reference took {time.perf_counter() - t_ref:.1f}s")

    values = {"train_items_per_s_chip": rate, "setup_s": setup_s}
    extra, breakdown = None, None
    if args.trace:
        run_ctx = {
            "counters": {"compiles_in_window": hook.compiles_in_window},
            "series": {"step_gap_ms": (gaps * 1e3).tolist()},
            "rates": {"items_per_s_chip": rate},
            "cfg": cfg, "traffic": traffic, "peaks": device["peaks"],
            "chips": cell["chips"], "traced_steps": hook.traced_steps}
        tr = trace_mod.load(trace_dir)
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)
        run_ctx["trace"] = tr
        values = readers.read_all(bench, cell["name"], run_ctx)
        extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
        breakdown = tr.breakdown(default_host="recipe.loop")
    common.emit_result(bench=bench, cell=cell["name"], trace=args.trace,
                       correct=ok, attempted=n_steps, failed=0,
                       values=values, device=device, compared=compared,
                       extra_device=extra, breakdown=breakdown)
    return ok


def _plant(mod, fault):
    """Test-only: break the timed path underneath the harness.
    ``half_batch``: the loss sees the first half of the rows and takes
    its mean over them. ``state_unchanged``: the step returns the state
    it was given. Returns the call that takes the fault out again."""
    amp = importlib.import_module(mod).amp
    orig = amp.make_train_step

    def planted(loss_fn, *a, **kw):
        if fault == "half_batch":
            return orig(lambda p, b: loss_fn(p, b[: b.shape[0] // 2]),
                        *a, **kw)
        init_fn, step_fn = orig(loss_fn, *a, **kw)

        def frozen(state, batch):
            return state, step_fn(state, batch)[1]
        return init_fn, frozen

    amp.make_train_step = planted
    return lambda: setattr(amp, "make_train_step", orig)
