#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Needs a TPU with as many chips as the cell asks for; anything
else exits non-zero with no result line. Set-up (weights from the seed, the
cell's own shapes, the ramp) is timed as ``setup_s``; then the window of
``--seconds``; then, with the program's device state freed, the output check
against the plain reference. The last stdout line is the result object;
everything else is on earlier lines. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window's last seconds.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
import types

T_IMPORT = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 6.0
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def since_process_start() -> float:
    """Seconds this process has lived (Linux: /proc), so that interpreter
    start-up and imports count as set-up."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def module_for(package: str, name: str):
    return importlib.import_module(
        f"benchmark.{package}.{name.replace('.', '_').replace('-', '_')}")


class CompileWatch:
    """Backend compiles and cache retrievals, as ``jax.monitoring`` reports
    them: seconds during set-up, a count inside the window."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.events += 1

    def _evt(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.events += 1


class Tracer:
    """Starts the profiler for the window's last ``TRACE_SECONDS``; the
    stall of starting it is inside the window and recorded."""

    def __init__(self, jax, on_start, seconds, keep=""):
        self.jax, self.on_start = jax, on_start
        self.seconds, self.keep = seconds, keep
        self.started_at = None
        self.stall_s = 0.0
        self.mark = None        # the driver's counters when the trace began

    def tick(self, now: float, t_end: float) -> None:
        if self.started_at is not None or now < t_end - self.seconds:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.started_at = time.perf_counter()
        self.stall_s = self.started_at - now
        self.mark = self.on_start()

    def stop(self):
        from benchmark import trace_reduce
        self.jax.profiler.stop_trace()
        try:
            path = trace_reduce.find_xplane(TRACE_DIR)
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(path, self.keep)
            return trace_reduce.reduce_file(path)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def ledger_compiles() -> dict:
    from senweaver_ide_tpu.obs.runtime_profile import get_profiler
    return {k: v.get("compiles", 0)
            for k, v in get_profiler().ledger().items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="also read the output check's control: the "
                         "reference rounded through fp8 | int8 | bf16")
    ap.add_argument("--engine-kv-dtype", default="",
                    help="the output check's other control: the engine "
                         "itself with kv_dtype int8 | fp8, through the "
                         "timed path; correct has to come out false")
    ap.add_argument("--trace-seconds", type=float, default=TRACE_SECONDS)
    ap.add_argument("--keep-trace", default="",
                    help="copy the xplane.pb of a traced run here")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the cell's control flow at the "
                         "sizes under benchmark/rehearsal/: prints no "
                         "device metric")
    args = ap.parse_args()

    from benchmark.manifest import Manifest, model_config, load_json
    man = Manifest(args.workload, args.rehearse)

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                               ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t_jax = since_process_start()
    devs = jax.devices()
    t_devs = since_process_start()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < man.chips):
        print(f"benchmark: cell {man.name} needs {man.chips} TPU chip(s); "
              f"JAX found {len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 3
    peaks_all = load_json(HERE, "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks_all and not args.rehearse:
        print(f"benchmark: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 3
    watch = CompileWatch(jax)

    ctx = types.SimpleNamespace(
        manifest=man, seed=args.seed, seconds=args.seconds,
        control=args.control, engine_kv_dtype=args.engine_kv_dtype, log=log,
        config=model_config(man.config), devices=devs[:man.chips])
    driver = module_for("drivers", man.traffic["driver"])

    # ---- set-up ----------------------------------------------------------
    state = driver.prepare(ctx)
    setup = dict(state.counters, compile_s=watch.compile_s,
                 compile_events=watch.events)
    compiles0, events0 = ledger_compiles(), watch.events
    tracer = (Tracer(jax, lambda: driver.trace_started(state),
                     args.trace_seconds, args.keep_trace)
              if args.trace else None)
    setup_s = since_process_start()
    log(f"set-up {setup_s:.3f} s (jax imported at {t_jax:.2f} s, devices "
        f"found at {t_devs:.2f} s; backend compile {watch.compile_s:.2f} s in "
        f"{watch.events} compile or cache events)")

    # ---- the window ------------------------------------------------------
    w = driver.window(ctx, state, tracer.tick if tracer else None)
    compiles1, events1 = ledger_compiles(), watch.events
    reduced = tracer.stop() if tracer and tracer.started_at else None
    stats = [d.memory_stats() or {} for d in ctx.devices]
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0)
                                        for s in stats), default=0),
              "memory_limit_bytes": max((s.get("bytes_limit", 0)
                                         for s in stats), default=0)}
    for note in driver.notes(w):
        log(note)
    if tracer and tracer.started_at:
        log(f"starting the trace stalled the loop {tracer.stall_s:.3f} s")

    # ---- outputs against the plain reference, program state freed --------
    t_check = time.perf_counter()
    record = types.SimpleNamespace(
        window=w, trace=reduced, setup=setup, compiles0=compiles0,
        compiles1=compiles1, device=device, config_file=man.config,
        peaks=peaks_all.get(kind),
        traced_steps=[], traced_prefill_tokens=0)
    if tracer and tracer.started_at:
        driver.traced_part(record, state, tracer)
    driver.release(state)
    compared, attempted, failed = driver.compared(ctx, state, w)
    from benchmark.correct import Compared
    compared.append(Compared("window_compiles", float(events1 - events0),
                             0.0))
    for c in compared:
        print(c.line(), flush=True)
    correct = all(c.ok for c in compared if not c.name.startswith("control_"))
    log(f"output check took {time.perf_counter() - t_check:.2f} s")

    # ---- the result line -------------------------------------------------
    metrics = {}
    if args.trace:
        for m in man.per_layer():
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            value = module_for("readers", spec["reader"]).read(
                record, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in man.end_to_end():
            value = (setup_s if m["name"] == "setup_s"
                     else module_for("e2e", m["name"]).read(w, ctx))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        from benchmark import trace_reduce
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top(reduced.ops),
            "idle_gaps": trace_reduce.top(reduced.gaps)}
    if args.rehearse:
        # names only: a CPU run's number never stands under a metric's name
        result["rehearsal"] = sorted(metrics)
        result["metrics"] = {}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
