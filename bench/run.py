#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  Everything about it is found by name:

* its configuration in ``bench/configs/<config>.json``, whose ``family``
  names the adapter in ``bench/families/`` (build, warm-up, work counts)
  and whose ``reference`` names the plain model in ``bench/reference/``;
* its traffic mix in ``bench/traffic/<traffic>.json``, read by
  ``bench/traffic.py``;
* each per-layer metric in ``bench/metrics/<name>.py``, a function
  ``read(run)`` of the traced run's record.

A run: make the weights and the inputs from ``--seed``; compile every
program shape the traffic reaches; serve the ramp that brings the cell to a
steady mix (all of that is ``setup_s``); drive ``Scheduler.step`` for
``--seconds`` while stamping each token as it reaches its request; then
compare a sample of the served tokens with the plain float32 reference.
With ``--trace 1`` the first seconds of the window are profiled and the
result carries the per-layer metrics; otherwise it carries the end-to-end
metrics.  The last line of standard output is the result, as JSON.

Without a TPU the run fails (exit 3) unless ``--rehearse`` is given: the
CPU rehearsal runs the configuration's and the mix's ``rehearsal`` sizes
and names the CPU as its platform.  ``bench/control.py`` runs the same
harness with the control in the program's place (``main(control=True)``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

#: Seconds of the window that a ``--trace 1`` run profiles.
TRACE_SECONDS = 5.0
#: Requests in the correctness sample: at least ``SAMPLE_REQUESTS``, and
#: more until they hold ``SAMPLE_TOKENS`` served tokens, at most
#: ``SAMPLE_MAX``.
SAMPLE_REQUESTS = 12
SAMPLE_TOKENS = 2048
SAMPLE_MAX = 24
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (persistent-cache fetches included)."""

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += duration


def _memory(jax):
    """Peak device bytes in use so far, on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_metric(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pct(values, q):
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Served:
    """One request as the harness sees it: when it was due, its token
    stamps, and the program's own request object."""

    def __init__(self, item, req, due_abs):
        self.item, self.req, self.due = item, req, due_abs
        self.times = []
        self.submitted = None
        self.finished_at = None


class Session:
    """The scheduler over one bound family, driven by one traffic mix."""

    def __init__(self, jax, family, chunk, traffic, mix, tracing):
        from repro.serve import Request, Scheduler

        self.jax = jax
        self.Request = Request
        self.family = family
        self.sched = Scheduler(family, chunk=chunk)
        self.traffic = traffic
        self.mix = mix
        self.tracing = tracing
        self.served = []
        self.calls = []
        self.closing = False
        if tracing:
            self._wrap_family()

    # -- spans ---------------------------------------------------------------

    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def _wrap_family(self):
        """Spans and a call record around each device-launching call.

        In traced runs only.  Each call's outputs are left as they are: the
        program's own syncs are unchanged.
        """
        import numpy as np

        fam = self.family
        for kind in ("prefill_batch", "decode_steps", "verify_steps"):
            orig = getattr(fam, kind)

            def call(*args, _orig=orig, _kind=kind):
                rec = {"kind": _kind}
                if _kind == "prefill_batch":
                    _, counts, slots, starts = args
                    counts = np.asarray(counts)
                    real = counts > 0
                    plen = {r.slot: r.prompt_len for r in self.sched.resident}
                    ends = np.asarray(starts) + counts
                    rec.update(
                        counts=counts[real].copy(),
                        starts=np.asarray(starts)[real].copy(),
                        completing=int(sum(
                            plen.get(int(s)) == int(e)
                            for s, e in zip(np.asarray(slots)[real],
                                            ends[real]))))
                else:
                    _, active, n = args
                    act = np.asarray(active, bool)
                    rec.update(n=int(n), lens=np.asarray(
                        fam.lengths())[act].astype(np.int64).copy())
                rec["index"] = len(self.calls)
                with self.jax.profiler.TraceAnnotation(
                        f"bench.{_kind}", call=rec["index"]):
                    out = _orig(*args)
                self.calls.append(rec)
                return out

            setattr(fam, kind, call)

    # -- requests -----------------------------------------------------------

    def submit(self, item, due_abs):
        s = Served(item, None, due_abs)
        s.submitted = time.perf_counter()

        def on_token(_req, _tok, s=s):
            s.times.append(time.perf_counter())

        def on_finish(_req, s=s):
            s.finished_at = time.perf_counter()
            if self.mix["loop"] == "closed" and not self.closing:
                self.submit(self.traffic.next_item(), time.perf_counter())

        s.req = self.Request(rid=item.rid, prompt=item.prompt,
                             max_new=item.max_new, on_token=on_token,
                             on_finish=on_finish)
        self.served.append(s)
        with self.span("bench.submit"):
            self.sched.submit(s.req, strict=False)

    def busy(self):
        return bool(self.sched.queue or self.sched.resident)

    def step(self):
        with self.span("bench.step"):
            self.sched.step()


def drive(sess, seconds, t0, schedule, trace_dir, counter, jax):
    """Serve from ``t0`` (the window's start) until ``t0 + seconds``.

    ``schedule`` holds open-loop items with due times relative to ``t0``,
    the ramp's before it.  Steps already under way at ``t0`` finish first.
    Returns the window's end (the close of the last step begun in it) and
    the compiles inside the window.
    """
    i = 0
    opened = False
    c0 = counter.n
    traced = None
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            opened, c0 = True, counter.n
            if trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced = jax.profiler.TraceAnnotation("bench.traced")
                traced.__enter__()
        if traced is not None and now >= t0 + TRACE_SECONDS:
            traced.__exit__(None, None, None)
            traced = None
            jax.profiler.stop_trace()
        if now >= end:
            break
        while i < len(schedule) and t0 + schedule[i].due <= now:
            sess.submit(schedule[i], t0 + schedule[i].due)
            i += 1
        if not sess.busy():
            nxt = t0 + schedule[i].due if i < len(schedule) else end
            with sess.span("bench.idle_wait"):
                time.sleep(max(0.0, min(nxt, end) - now))
            continue
        sess.step()
    t1 = time.perf_counter()
    if traced is not None:
        traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return t1, counter.n - c0


def end_to_end(sess, t0, t1, seconds, mix):
    """The cell's end-to-end numbers, and the lines printed before them."""
    import numpy as np

    tokens = sum(sum(1 for t in s.times if t0 <= t <= t1) for s in sess.served)
    out = {"output_tok_s": tokens / (t1 - t0)}
    lines = [f"window {t1 - t0:.3f} s, {tokens} output tokens"]
    due = [s for s in sess.served if t0 <= s.due < t0 + seconds]
    if mix["loop"] == "open":
        ttft = [((s.times[0] if s.times and s.times[0] <= t1 else t1) - s.due)
                * 1e3 for s in due]
        tpot = []
        for s in due:
            ts = [t for t in s.times if t0 <= t <= t1]
            if len(ts) >= 2:
                tpot.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
        late = [(s.submitted - s.due) * 1e3 for s in due]
        out["ttft_p95_ms"] = _pct(ttft, 95) if ttft else None
        out["tpot_p95_ms"] = _pct(tpot, 95) if tpot else None
        lines.append(f"requests due {len(due)}; ttft p50 "
                     f"{_pct(ttft, 50) if ttft else float('nan'):.1f} ms p95 "
                     f"{out['ttft_p95_ms'] or float('nan'):.1f} ms; tpot over "
                     f"{len(tpot)} requests p50 "
                     f"{_pct(tpot, 50) if tpot else float('nan'):.2f} ms p95 "
                     f"{out['tpot_p95_ms'] or float('nan'):.2f} ms")
        if late:
            lines.append(f"generator lateness p50 {_pct(late, 50):.2f} ms "
                         f"max {max(late):.2f} ms")
        unserved = sum(1 for s in due if not s.times)
        lines.append(f"due requests without a first token at the close: "
                     f"{unserved}")
    else:
        done = [s for s in sess.served
                if s.finished_at is not None and t0 <= s.finished_at <= t1]
        lat = [(s.finished_at - s.due) for s in done]
        if lat:
            lines.append(f"requests finished {len(done)}; latency p50 "
                         f"{_pct(lat, 50):.2f} s p95 {_pct(lat, 95):.2f} s")
    return out, lines


def sample(sess, seed, t0, t1):
    """Finished requests whose served tokens are compared, drawn from the
    seed, the longest always among them."""
    import numpy as np

    done = [s for s in sess.served if s.finished_at is not None
            and s.req.generated and len(s.req.generated) == s.item.max_new]
    in_window = [s for s in done if t0 <= s.finished_at <= t1]
    pool = in_window if sum(len(s.req.generated) for s in in_window) \
        >= SAMPLE_TOKENS else done
    if not pool:
        return []
    longest = max(pool, key=lambda s: s.item.prompt.size + s.item.max_new)
    rng = np.random.default_rng([int(seed) % (1 << 63), 4])
    picked, tokens = [longest], len(longest.req.generated)
    for j in rng.permutation(len(pool)):
        if len(picked) >= SAMPLE_MAX or (len(picked) >= SAMPLE_REQUESTS
                                         and tokens >= SAMPLE_TOKENS):
            break
        if pool[j] is not longest:
            picked.append(pool[j])
            tokens += len(pool[j].req.generated)
    return picked


def _positions(picked, mix_max):
    """Each pair's sequence (prompt, then served tokens but the last), the
    rows whose logits chose the served tokens, and the shared padding."""
    import numpy as np

    seqs = [np.concatenate([prompt, gen[:-1]]) for prompt, gen in picked]
    rows = [prompt.size - 1 + np.arange(gen.size) for prompt, gen in picked]
    return seqs, rows, -(-mix_max["total"] // 256) * 256, mix_max["output"]


def compare(fam_mod, cfg, mix_max, seed, picked):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over the sample of (prompt, served tokens) pairs."""
    import numpy as np

    seqs, rows, pad_to, pad_rows = _positions(picked, mix_max)
    ref = fam_mod.reference(cfg, seed, seqs, rows, pad_to, pad_rows)
    gaps, flips = [], 0
    for lg, (_, tok) in zip(ref, picked):
        gaps.append(lg.max(axis=-1) - lg[np.arange(tok.size), tok])
        flips += int((lg.argmax(axis=-1) != tok).sum())
    return {"served_gap": float(np.concatenate(gaps).max()),
            "served_tokens": int(sum(t.size for _, t in picked)),
            "requests": len(picked), "argmax_differs": flips}


def control_tokens(fam_mod, cfg, mix_max, seed, picked):
    """The control in the program's place: at each position of the same
    prompts and served tokens, the token that the control (the reference
    at the precision below the configuration's) puts first."""
    import numpy as np

    seqs, rows, pad_to, pad_rows = _positions(picked, mix_max)
    ctl = fam_mod.reference(cfg, seed, seqs, rows, pad_to, pad_rows,
                            control=True)
    return [(prompt, lg.argmax(axis=-1).astype(np.int32))
            for (prompt, _), lg in zip(picked, ctl)]


def main(argv=None, control=False) -> int:
    """One run; with ``control`` the control's tokens are compared in
    place of the program's (``bench/control.py``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the rehearsal sizes")
    args = ap.parse_args(argv)

    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("the program (src/repro) is not in this checkout")
        return 2
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # A fixed directory inside the checkout: only a cell's first run here
    # compiles, and two checkouts never share a cache.
    cache_dir = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache_dir, exist_ok=True)      # JAX writes into, never makes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    cache_entries = len(os.listdir(cache_dir))

    import jax
    import numpy as np
    from repro.runtime.device import describe_devices, use_compile_cache

    from bench.traffic import Traffic, load_mix

    device = describe_devices()
    if device["platform"] == "tpu":
        use_compile_cache()
        # Keep every program, however quick its compile: a later run then
        # only loads what this one compiled.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < cell["chips"]):
        log(f"no TPU with {cell['chips']} chip(s) here: JAX found "
            f"{device['count']} {device['platform']} device(s)")
        return 3
    peaks = None
    if device["platform"] == "tpu":
        table = _load_json(BENCH, "peaks.json")
        if device["kind"] not in table:
            log(f"device kind {device['kind']!r} is not in bench/peaks.json")
            return 3
        peaks = table[device["kind"]]
    counter = CompileCounter(jax)

    cfg = _load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = load_mix(cell["traffic"])
    if args.rehearse:
        cfg.update(cfg.get("rehearsal", {}))
        mix.update(mix.get("rehearsal", {}))
    fam_mod = importlib.import_module(f"bench.families.{cfg['family']}")
    traffic = Traffic(mix, args.seed, cfg["vocab_size"])
    lens = traffic.max_lengths()
    reach = dict(lens, in_flight=(mix["clients"] if mix["loop"] == "closed"
                                  else cfg["slots"]))
    mix_max = {"total": int(traffic.pool.sum(axis=1).max()),
               "output": lens["output"]}

    t_build = time.perf_counter()
    family = fam_mod.build(cfg, args.seed)
    t_warm = time.perf_counter()
    n_warm = fam_mod.warm(family, cfg, reach)
    t_ramp = time.perf_counter()
    sess = Session(jax, family, cfg["prefill_chunk"], traffic, mix,
                   tracing=bool(args.trace))
    trace_tmp = trace_dir = None
    if args.trace:
        trace_tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        trace_dir = trace_tmp.name
    if mix["loop"] == "closed":
        for item in traffic.first_items():
            sess.submit(item, time.perf_counter())
        for _ in range(int(mix["ramp_steps"])):
            sess.step()
        schedule = []
        t0 = time.perf_counter()
    else:
        ramp = float(mix["ramp_s"])
        schedule = traffic.schedule(ramp + args.seconds)
        for it in schedule:
            it.due -= ramp
        t0 = time.perf_counter() + ramp
    setup_s = t0 - T_START
    compiles_setup = counter.n
    t1, compiles_window = drive(sess, args.seconds, t0, schedule, trace_dir,
                                counter, jax)
    sess.closing = True
    e2e, lines = end_to_end(sess, t0, t1, args.seconds, mix)
    lines.append(f"setup {setup_s:.3f} s: imports {t_build - T_START:.1f} s, "
                 f"weights and pools {t_warm - t_build:.1f} s, warm-up of "
                 f"{n_warm} programs {t_ramp - t_warm:.1f} s, ramp "
                 f"{t0 - t_ramp:.1f} s; {compiles_setup} compiles "
                 f"({counter.secs:.1f} s)")
    lines.append(f"compiles in the window: {compiles_window}")
    lines.insert(0, "compile cache: " + (
        "cold (empty directory)" if cache_entries == 0
        else f"warm ({cache_entries} entries at start)"))
    memory_peak = _memory(jax)

    attempted = sum(1 for s in sess.served if t0 <= s.due < t0 + args.seconds)
    failed = sum(1 for s in sess.served
                 if t0 <= s.due < t0 + args.seconds
                 and s.req.reject_reason is not None)
    picked = [(s.item.prompt, np.asarray(s.req.generated, np.int32))
              for s in sample(sess, args.seed, t0, t1)]
    record = SimpleNamespace(calls=sess.calls, cfg=cfg, fam=fam_mod,
                             peaks=peaks, compiles_in_window=compiles_window,
                             trace=None)
    # Free the program's state before the reference runs: nothing kept
    # from here on refers to the session, its requests or the family.
    sess = family = None
    gc.collect()

    breakdown = None
    if args.trace:
        from bench import trace as trace_mod
        with open(os.path.join(trace_dir, "calls.json"), "w") as f:
            json.dump([{k: v.tolist() if hasattr(v, "tolist") else v
                        for k, v in c.items()} for c in record.calls], f)
        record.trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        trace_tmp.cleanup()
        breakdown = {"device_ops": record.trace.top_ops(10),
                     "idle_gaps": record.trace.top_gaps(10)}

    checks = {}
    correct = False
    if picked:
        t_check = time.perf_counter()
        if control:
            own = compare(fam_mod, cfg, mix_max, args.seed, picked)
            lines.append(f"program served_gap {own['served_gap']!r}")
            picked = control_tokens(fam_mod, cfg, mix_max, args.seed, picked)
            lines.append("control: the control's tokens are compared")
        cmp = compare(fam_mod, cfg, mix_max, args.seed, picked)
        limit = cfg["limits"]["served_gap"]
        checks["served_gap"] = {"value": cmp["served_gap"], "limit": limit}
        correct = cmp["served_gap"] <= limit
        lines.append(f"compared {cmp['served_tokens']} served tokens of "
                     f"{cmp['requests']} requests in "
                     f"{time.perf_counter() - t_check:.1f} s; reference "
                     f"argmax differs at {cmp['argmax_differs']}")
    else:
        lines.append("no finished request to compare")

    names = [m for m in bench["end_to_end"]
             if args.workload in m.get("workloads", [args.workload])]
    if args.trace:
        reported = {m["name"] for m in names}
        names = [m for m in bench["per_layer"]
                 if args.workload in m.get("workloads", [args.workload])
                 and m["moves"] in reported]
    metrics = {}
    values = dict(e2e, setup_s=setup_s)
    for m in names:
        if args.trace:
            value = _load_metric(m["name"]).read(record)
        else:
            value = values.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    if args.trace:
        dev.update(busy_s=record.trace.busy_s(),
                   window_s=record.trace.window_s)
    for line in lines:
        print(line, flush=True)
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
