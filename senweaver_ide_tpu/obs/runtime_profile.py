"""Runtime performance observatory for jit/pjit callables.

The static analysis pass (analysis/jit_lint.py JIT201-203) PREDICTS
retrace storms from source shape; this module PROVES what the runtime
actually did. :class:`ProfiledFunction` wraps a jitted callable and
maintains, per wrapped function:

- a **compile/retrace ledger**: compile count + compile wall time per
  distinct abstract signature (shape/dtype fingerprint of the args),
  cross-checked against the jit cache (``_cache_size``) so a compile is
  counted only when the runtime really traced, with a named
  retrace-storm detector (``senweaver_runtime_retrace_storms_total``);
- per-call **device-time histograms** — with ``block=True`` (the
  default) the wrapper blocks on the outputs, so the window covers the
  device step, not just its dispatch. Every wired hot path syncs on its
  outputs immediately after the call anyway (the engine's single
  batched ``device_get`` per step), so blocking here moves the existing
  sync, it does not add one;
- two **spans** a call, ``<name>.dispatch`` (the wrapped call: trace,
  compile, enqueue) and, when blocking, ``<name>.wait`` (its
  ``block_until_ready``), under whatever span is open around the call —
  so the caller's span minus these two is the wrapper's own
  bookkeeping plus whatever else the caller did;
- **host→device transfer accounting**: bytes of host-resident (numpy)
  leaves fed per call — PR 10 showed the host feed is where wins hide.
  ``profiled_device_get`` is the device→host counterpart;
- **XLA cost analysis** (``lowered.compile().cost_analysis()``): FLOPs
  and bytes touched per compiled signature, turned into
  achieved-vs-roofline utilization gauges against the device's
  published peaks (:data:`DEVICE_PEAKS`, by ``device_kind``; a device
  that is not in the table gets no utilization gauge). OFF by
  default (it costs one extra trace+compile per new signature) — enable
  with ``get_profiler().set_cost_analysis(True)`` or
  ``SENWEAVER_RUNTIME_COST_ANALYSIS=1``;
- **unqueued time** for a function whose caller times the step itself
  (``begin_step`` / ``end_step``): the time between one step's results
  reaching the host and the same caller's next launch, in which that
  caller had no such step in flight (``unqueued_ms_sum``,
  ``senweaver_runtime_unqueued_ms``; a wait for work counts too) —
  with no profiler attached and one busy engine on the device,
  ``unqueued / (unqueued + step)`` is a lower bound of the share of
  time the device waited for the host;
- **HBM/live-buffer watermark sampling** (:func:`sample_memory`):
  ``device.memory_stats()`` where the backend provides it (TPU/GPU),
  degrading to live-array byte accounting on CPU — the gauges carry a
  ``backend`` label so dashboards never mix CPU and TPU watermarks.

Compile wall time comes from ``jax.monitoring`` duration events
(``/jax/core/compile/*``) attributed to the in-flight call via a
thread-local frame stack, so it reflects real trace+lower+backend time
rather than first-call-minus-steady guesswork.

Everything exports as ``senweaver_runtime_*`` metrics through the
process-global registry (resolved per publish, so ``_reset_for_tests``
isolation works) and as a JSONL ledger for ``scripts/obs_report.py
--runtime``. Profiling is on by default (a handful of dict writes per
call); ``SENWEAVER_RUNTIME_PROFILE=0`` or
``get_profiler().set_enabled(False)`` turns the wrappers into plain
pass-throughs.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import jax
import numpy as np

from .metrics import DEFAULT_MS_BUCKETS
from .tracing import get_tracer

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"

# Published peaks of one chip, by ``device_kind`` (the v5e's are Google
# Cloud's "TPU v5e" page: 197 TFLOP/s bf16, 819 GB/s of HBM; the
# benchmark's ``peaks.json`` holds the same two). A device that is not
# here has no utilization: nothing is guessed.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def device_kind() -> Optional[str]:
    """``device_kind`` of the first device of the backend in use."""
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return None


def device_peaks() -> Dict[str, float]:
    """This process's row of :data:`DEVICE_PEAKS`; empty for a device
    the table does not have."""
    return DEVICE_PEAKS.get(device_kind() or "", {})

# The wrapper's two spans are children: on under an enabled tracer or a
# span that is open around the call, and never ask the profiler
# themselves — a caller that found tracing off (the engine asks once a
# step) pays one context lookup each here.
def _child_span(name: str):
    return get_tracer().child_span(name)

# Compile-time buckets: compiles run seconds, not microseconds.
COMPILE_MS_BUCKETS: Tuple[float, ...] = (
    10.0, 50.0, 100.0, 250.0, 500.0, 1_000.0, 2_500.0, 5_000.0,
    10_000.0, 30_000.0, 60_000.0, 300_000.0)

_tls = threading.local()


def _frames() -> List[Dict[str, float]]:
    st = getattr(_tls, "frames", None)
    if st is None:
        st = _tls.frames = []
    return st


def _on_event_duration(event: str, duration: float, **kw: Any) -> None:
    """jax.monitoring listener: compile-phase durations land on the
    innermost in-flight ProfiledFunction call of THIS thread (XLA
    compiles on the calling thread)."""
    if not str(event).startswith(_COMPILE_EVENT_PREFIX):
        return
    st = getattr(_tls, "frames", None)
    if st:
        st[-1]["compile_s"] += float(duration)


_listener_lock = threading.Lock()
_listener_installed = False


def _install_compile_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        _listener_installed = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)


# -- abstract signatures -------------------------------------------------

def _leaf_fingerprint(x: Any) -> Any:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype))
    if x is None or isinstance(x, (bool, int, float, str, bytes)):
        return x
    # static config objects (ModelConfig, SampleParams, optimizers):
    # identity by repr — good enough to separate compile cache keys
    return repr(x)[:160]


def _scan_tree(tree: Any) -> Tuple[Any, int]:
    """(hashable fingerprint, host-resident bytes) of one argument."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h2d = 0
    fps = []
    for leaf in leaves:
        fps.append(_leaf_fingerprint(leaf))
        if isinstance(leaf, np.ndarray):
            h2d += int(leaf.nbytes)
    return (treedef, tuple(fps)), h2d


def signature_of(args: Sequence[Any], kwargs: Dict[str, Any],
                 skip_args: Sequence[int] = (),
                 skip_kwargs: Sequence[str] = ()) -> Tuple[Tuple, int]:
    """Abstract-signature fingerprint of a call + host-feed bytes.

    ``skip_args``/``skip_kwargs`` name shape-stable arguments (params
    trees, configs) excluded from the scan — retraces they cause are
    still COUNTED via the jit cache size, just attributed to the
    coarser signature."""
    skip = frozenset(skip_args)
    skipk = frozenset(skip_kwargs)
    sig: List[Any] = []
    h2d = 0
    for i, a in enumerate(args):
        if i in skip:
            sig.append(("skip", i))
            continue
        fp, b = _scan_tree(a)
        sig.append(fp)
        h2d += b
    for k in sorted(kwargs):
        if k in skipk:
            sig.append(("skip", k))
            continue
        fp, b = _scan_tree(kwargs[k])
        sig.append((k, fp))
        h2d += b
    return tuple(sig), h2d


def _tree_nbytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


# -- ledger --------------------------------------------------------------

class _SigEntry:
    __slots__ = ("calls", "compiles", "compile_ms")

    def __init__(self) -> None:
        self.calls = 0
        self.compiles = 0
        self.compile_ms = 0.0


class _FnLedger:
    """Per-wrapped-function ledger. All mutation happens under the
    owning profiler's lock."""

    def __init__(self, name: str, storm_threshold: int,
                 blocking: bool) -> None:
        self.name = name
        self.storm_threshold = storm_threshold
        self.blocking = blocking
        self.calls = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self.storms = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.step_ms_sum = 0.0
        self.last_step_ms = 0.0
        # caller-timed steps (begin_step / end_step): the time between
        # a caller's end_step and its next begin_step
        self.unqueued_ms_sum = 0.0
        self.signatures: Dict[Tuple, _SigEntry] = {}
        # cost analysis per signature: sig -> (flops, bytes) or None
        self.cost: Dict[Tuple, Optional[Tuple[float, float]]] = {}

    def snapshot(self) -> Dict[str, Any]:
        sigs = []
        for sig, e in self.signatures.items():
            sigs.append({"key": repr(sig), "calls": e.calls,
                         "compiles": e.compiles,
                         "compile_ms": round(e.compile_ms, 3)})
        costs = [c for c in self.cost.values() if c is not None]
        flops = max((c[0] for c in costs), default=None)
        cbytes = max((c[1] for c in costs), default=None)
        return {
            "fn": self.name, "calls": self.calls,
            "compiles": self.compiles,
            "compile_ms": round(self.compile_ms, 3),
            "storms": self.storms,
            "storm_threshold": self.storm_threshold,
            "h2d_bytes": self.h2d_bytes, "d2h_bytes": self.d2h_bytes,
            "step_ms_sum": round(self.step_ms_sum, 3),
            "last_step_ms": round(self.last_step_ms, 3),
            "unqueued_ms_sum": round(self.unqueued_ms_sum, 3),
            "blocking": self.blocking,
            "flops_per_call": flops, "cost_bytes_per_call": cbytes,
            "signatures": sigs,
        }


class RuntimeProfiler:
    """Process-global home of every :class:`ProfiledFunction` ledger.

    Publishes ``senweaver_runtime_*`` into the global metrics registry
    (re-resolved whenever the global is swapped, so test isolation via
    ``obs._reset_for_tests`` holds)."""

    def __init__(self, *, enabled: Optional[bool] = None,
                 cost_analysis: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get(
                "SENWEAVER_RUNTIME_PROFILE", "1") != "0"
        if cost_analysis is None:
            cost_analysis = os.environ.get(
                "SENWEAVER_RUNTIME_COST_ANALYSIS", "0") == "1"
        self.enabled = enabled
        self._lock = threading.Lock()
        self._ledgers: Dict[str, _FnLedger] = {}    # guarded-by: _lock
        self._cost_analysis = cost_analysis
        self._registry = None                        # guarded-by: _lock
        self._instruments: Dict[str, Any] = {}       # guarded-by: _lock
        self._hbm_watermark: Dict[str, float] = {}   # guarded-by: _lock
        # functions whose caller times the step itself (``begin_step``)
        self._caller_steps: set = set()              # guarded-by: _lock
        self.storm_events: List[Dict[str, Any]] = []  # guarded-by: _lock
        _install_compile_listener()

    # -- switches ----------------------------------------------------------
    def set_enabled(self, on: bool) -> None:
        self.enabled = bool(on)

    def set_cost_analysis(self, on: bool) -> None:
        self._cost_analysis = bool(on)

    @property
    def cost_analysis_enabled(self) -> bool:
        return self._cost_analysis

    # -- instruments -------------------------------------------------------
    def _metrics(self) -> Dict[str, Any]:
        """Instrument cache keyed to the CURRENT global registry;
        rebuilt when the global is swapped (test isolation)."""
        from . import get_registry
        reg = get_registry()
        with self._lock:
            if reg is self._registry:
                return self._instruments
            ins = {
                "calls": reg.counter(
                    "senweaver_runtime_calls_total",
                    "Profiled jit-callable invocations.",
                    labelnames=("fn",)),
                "compiles": reg.counter(
                    "senweaver_runtime_compiles_total",
                    "Traces+compiles observed per profiled callable "
                    "(one per distinct abstract signature on a healthy "
                    "path).", labelnames=("fn",)),
                "compile_ms": reg.histogram(
                    "senweaver_runtime_compile_ms",
                    "Wall time of each observed trace+compile.",
                    labelnames=("fn",), buckets=COMPILE_MS_BUCKETS),
                "step_ms": reg.histogram(
                    "senweaver_runtime_step_ms",
                    "Per-call wall time (device window when the "
                    "wrapper blocks on outputs, dispatch otherwise).",
                    labelnames=("fn",), buckets=DEFAULT_MS_BUCKETS),
                "unqueued_ms": reg.histogram(
                    "senweaver_runtime_unqueued_ms",
                    "Time between a caller-timed step's results "
                    "reaching the host and that caller's next launch: "
                    "it had no such step in flight.",
                    labelnames=("fn",), buckets=DEFAULT_MS_BUCKETS),
                "storms": reg.counter(
                    "senweaver_runtime_retrace_storms_total",
                    "Retrace-storm detector trips: compiles exceeded "
                    "the per-fn threshold AND outnumber cache reuse "
                    "(runtime counterpart of static JIT201-203).",
                    labelnames=("fn",)),
                "transfer": reg.counter(
                    "senweaver_runtime_transfer_bytes_total",
                    "Host<->device bytes moved by profiled calls.",
                    labelnames=("fn", "direction")),
                "signatures": reg.gauge(
                    "senweaver_runtime_signatures",
                    "Distinct abstract signatures seen per callable "
                    "(the compile-cache footprint).",
                    labelnames=("fn",)),
                "flops": reg.gauge(
                    "senweaver_runtime_flops_per_call",
                    "XLA cost_analysis FLOPs of the largest compiled "
                    "signature.", labelnames=("fn",)),
                "cost_bytes": reg.gauge(
                    "senweaver_runtime_bytes_per_call",
                    "XLA cost_analysis bytes accessed of the largest "
                    "compiled signature.", labelnames=("fn",)),
                "achieved": reg.gauge(
                    "senweaver_runtime_achieved_flops_per_sec",
                    "cost_analysis FLOPs / measured device window of "
                    "the last profiled call.", labelnames=("fn",)),
                "roofline": reg.gauge(
                    "senweaver_runtime_roofline_utilization",
                    "Achieved / peak per resource (published peaks of "
                    "the device kind; absent for a kind the program's "
                    "table does not have).",
                    labelnames=("fn", "resource")),
            }
            self._registry = reg
            self._instruments = ins
            return ins

    # -- ledger access -----------------------------------------------------
    def _ledger(self, name: str, storm_threshold: int,
                blocking: bool) -> _FnLedger:
        with self._lock:
            led = self._ledgers.get(name)
            if led is None:
                led = self._ledgers[name] = _FnLedger(
                    name, storm_threshold, blocking)
            return led

    def ledger(self) -> Dict[str, Dict[str, Any]]:
        """JSON-friendly snapshot of every function's ledger."""
        with self._lock:
            return {name: led.snapshot()
                    for name, led in self._ledgers.items()}

    def export_jsonl(self, path: str) -> int:
        """One JSON line per profiled function (obs_report --runtime
        reads this); returns the number of lines written."""
        snap = self.ledger()
        with open(path, "w") as f:
            for name in sorted(snap):
                f.write(json.dumps(snap[name]) + "\n")
        return len(snap)

    def flops_per_call(self, name: str) -> Optional[float]:
        """Largest cost_analysis FLOPs figure recorded for ``name``
        (None until a compiled signature was analyzed)."""
        with self._lock:
            led = self._ledgers.get(name)
            if led is None:
                return None
            costs = [c[0] for c in led.cost.values() if c is not None]
            return max(costs) if costs else None

    def utilization(self, name: str) -> Optional[Dict[str, float]]:
        """Achieved FLOP/s (and utilization against the device kind's
        published peak, where :data:`DEVICE_PEAKS` has the kind) from
        the last blocking call's device window."""
        with self._lock:
            led = self._ledgers.get(name)
            if led is None or not led.blocking or led.last_step_ms <= 0:
                return None
            costs = [c[0] for c in led.cost.values() if c is not None]
            if not costs:
                return None
            achieved = max(costs) / (led.last_step_ms / 1_000.0)
        out = {"achieved_flops_per_sec": achieved}
        peak = device_peaks().get("flops_per_s")
        if peak:
            out["utilization"] = achieved / peak
        return out

    # -- recording ---------------------------------------------------------
    def account_transfer(self, name: str, nbytes: int,
                         direction: str = "h2d") -> None:
        if not self.enabled or nbytes <= 0:
            return
        led = self._ledger(name, 10, False)
        with self._lock:
            if direction == "d2h":
                led.d2h_bytes += int(nbytes)
            else:
                led.h2d_bytes += int(nbytes)
        self._metrics()["transfer"].inc(
            int(nbytes), fn=name, direction=direction)

    def begin_step(self, name: str) -> float:
        """The caller of a function wrapped with ``block=False`` times
        the step itself, from here to :meth:`end_step`: it knows when
        the results are on the host, the wrapper sees the dispatch
        alone and leaves ``name``'s step times to the caller from now
        on. Returns the clock reading to hand to ``end_step``."""
        if not self.enabled:
            return 0.0
        if name not in self._caller_steps:
            with self._lock:
                self._caller_steps.add(name)
        return time.perf_counter()

    def end_step(self, name: str, t_begin: float,
                 last_end: float = 0.0) -> float:
        """The step begun at ``t_begin`` has its results on the host:
        its time goes to ``name``'s ledger (``step_ms_sum``,
        ``last_step_ms``) and the step histogram. Returns the clock
        reading it took (0.0 where it recorded nothing), for the caller
        to keep and hand back as ``last_end`` at its next step: the time
        from there to ``t_begin`` — the caller had no step of ``name``
        in flight — goes to ``unqueued_ms_sum`` and its histogram, 0
        where this step was begun before the last one ended (a caller
        that runs a step ahead: one was in flight all the time). The
        reading is the CALLER's, not the ledger's: several engines step
        in one process under one name, and one's launch may precede
        another's fetch."""
        if not self.enabled:
            return 0.0
        t_end = time.perf_counter()
        step_ms = (t_end - t_begin) * 1_000.0
        unqueued_ms = None
        with self._lock:
            led = self._ledgers.get(name)
            if led is None:
                return 0.0
            led.step_ms_sum += step_ms
            led.last_step_ms = step_ms
            if last_end > 0.0:
                unqueued_ms = max(0.0, t_begin - last_end) * 1_000.0
                led.unqueued_ms_sum += unqueued_ms
        ins = self._metrics()
        ins["step_ms"].observe(step_ms, fn=name)
        if unqueued_ms is not None:
            ins["unqueued_ms"].observe(unqueued_ms, fn=name)
        return t_end

    def maybe_cost_analysis(self, pf: "ProfiledFunction", sig: Tuple,
                            args: Tuple, kwargs: Dict[str, Any]
                            ) -> Optional[Tuple[float, float]]:
        """Once per new signature when enabled: AOT lower+compile the
        wrapped callable and read flops / bytes accessed. Best-effort —
        any failure caches None so it is never retried per call."""
        led = self._ledger(pf.profile_name, pf.storm_threshold, pf.block)
        with self._lock:
            if not self._cost_analysis or sig in led.cost:
                return led.cost.get(sig)
        cost: Optional[Tuple[float, float]] = None
        try:
            lowered = pf.wrapped.lower(*args, **kwargs)
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                cost = (float(ca.get("flops", 0.0)),
                        float(ca.get("bytes accessed", 0.0)))
        except Exception:
            cost = None
        with self._lock:
            led.cost[sig] = cost
        return cost

    def record_call(self, pf: "ProfiledFunction", sig: Tuple, *,
                    compiled: bool, compile_s: float, step_ms: float,
                    h2d_bytes: int,
                    cost: Optional[Tuple[float, float]]) -> None:
        name = pf.profile_name
        led = self._ledger(name, pf.storm_threshold, pf.block)
        compile_ms = compile_s * 1_000.0
        storm = False
        with self._lock:
            led.calls += 1
            timed = pf.block or name not in self._caller_steps
            if timed:
                led.step_ms_sum += step_ms
                led.last_step_ms = step_ms
            led.h2d_bytes += h2d_bytes
            entry = led.signatures.get(sig)
            if entry is None:
                entry = led.signatures[sig] = _SigEntry()
            entry.calls += 1
            if compiled:
                led.compiles += 1
                led.compile_ms += compile_ms
                entry.compiles += 1
                entry.compile_ms += compile_ms
                # Storm: the compile set exceeded its budget AND the
                # cache is missing more often than it hits — a healthy
                # bucket ladder amortizes (calls >> compiles).
                if (led.compiles >= led.storm_threshold
                        and led.compiles * 2 > led.calls):
                    storm = True
                    led.storms += 1
                    self.storm_events.append({
                        "fn": name, "compiles": led.compiles,
                        "calls": led.calls, "signature": repr(sig)})
                    del self.storm_events[:-50]
            n_sigs = len(led.signatures)
        ins = self._metrics()
        ins["calls"].inc(fn=name)
        if timed:
            ins["step_ms"].observe(step_ms, fn=name)
        ins["signatures"].set(n_sigs, fn=name)
        if h2d_bytes > 0:
            ins["transfer"].inc(h2d_bytes, fn=name, direction="h2d")
        if compiled:
            ins["compiles"].inc(fn=name)
            ins["compile_ms"].observe(compile_ms, fn=name)
        if storm:
            ins["storms"].inc(fn=name)
        if cost is not None:
            flops, cbytes = cost
            ins["flops"].set(flops, fn=name)
            ins["cost_bytes"].set(cbytes, fn=name)
            if pf.block and step_ms > 0:
                step_s = step_ms / 1_000.0
                ins["achieved"].set(flops / step_s, fn=name)
                peaks = device_peaks()
                peak = peaks.get("flops_per_s")
                if peak:
                    ins["roofline"].set(flops / step_s / peak,
                                        fn=name, resource="flops")
                peak_bw = peaks.get("bytes_per_s")
                if peak_bw and cbytes:
                    ins["roofline"].set(cbytes / step_s / peak_bw,
                                        fn=name, resource="bytes")

    # -- HBM / live-buffer watermarks --------------------------------------
    def sample_memory(self) -> Dict[str, Dict[str, Any]]:
        """Per-backend memory watermarks, published with a ``backend``
        label. Uses ``device.memory_stats()`` where the runtime
        provides it; a backend without stats (CPU) degrades to
        live-array byte accounting — never raises."""
        from . import get_registry
        reg = get_registry()
        in_use = reg.gauge(
            "senweaver_runtime_hbm_bytes_in_use",
            "Device memory in use (memory_stats where available, "
            "live-array bytes otherwise).", labelnames=("backend",))
        limit_g = reg.gauge(
            "senweaver_runtime_hbm_bytes_limit",
            "Device memory capacity (memory_stats backends only).",
            labelnames=("backend",))
        peak_g = reg.gauge(
            "senweaver_runtime_hbm_watermark_bytes",
            "High-water mark of device memory in use.",
            labelnames=("backend",))
        live_g = reg.gauge(
            "senweaver_runtime_live_buffer_bytes",
            "Bytes held by live jax arrays (the CPU fallback "
            "accounting, sampled everywhere for cross-checks).",
            labelnames=("backend",))
        by_backend: Dict[str, Dict[str, Any]] = {}
        try:
            devices = jax.devices()
        except Exception:
            devices = []
        for d in devices:
            platform = getattr(d, "platform", "unknown")
            agg = by_backend.setdefault(
                platform, {"backend": platform, "source": "live_arrays",
                           "bytes_in_use": 0, "bytes_limit": 0,
                           "peak_bytes": 0})
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats:
                agg["source"] = "memory_stats"
                agg["bytes_in_use"] += int(stats.get("bytes_in_use", 0))
                agg["bytes_limit"] += int(stats.get("bytes_limit", 0))
                agg["peak_bytes"] += int(
                    stats.get("peak_bytes_in_use",
                              stats.get("bytes_in_use", 0)))
        live_bytes = 0
        try:
            for a in jax.live_arrays():
                try:
                    live_bytes += int(a.nbytes)
                except Exception:
                    continue
        except Exception:
            live_bytes = 0
        for platform, agg in by_backend.items():
            if agg["source"] == "live_arrays":
                agg["bytes_in_use"] = live_bytes
                agg["peak_bytes"] = live_bytes
            agg["live_buffer_bytes"] = live_bytes
            with self._lock:
                peak = max(self._hbm_watermark.get(platform, 0.0),
                           float(agg["peak_bytes"]),
                           float(agg["bytes_in_use"]))
                self._hbm_watermark[platform] = peak
            agg["watermark_bytes"] = peak
            in_use.set(agg["bytes_in_use"], backend=platform)
            peak_g.set(peak, backend=platform)
            live_g.set(live_bytes, backend=platform)
            if agg["bytes_limit"]:
                limit_g.set(agg["bytes_limit"], backend=platform)
        return by_backend


# -- the wrapper ---------------------------------------------------------

class ProfiledFunction:
    """Transparent profiling wrapper around a jit/pjit callable.

    Call syntax, donation, and static-arg handling pass through
    untouched; ``.lower``/``._cache_size``/etc. delegate to the wrapped
    callable. The GLOBAL profiler is resolved per call (same pattern as
    ``obs.get_tracer``), so swapping it for test isolation works.

    ``skip_args``/``skip_kwargs`` name shape-stable arguments (params
    trees, static configs) left out of the per-call signature scan to
    keep wrapper overhead off the hot path; retraces they cause are
    still counted via the jit cache size. ``block=False`` preserves
    async-dispatch semantics (trainer) at the price of the step
    histogram recording dispatch rather than device time.
    """

    def __init__(self, fn: Callable, name: str, *,
                 skip_args: Sequence[int] = (),
                 skip_kwargs: Sequence[str] = (),
                 block: bool = True,
                 storm_threshold: int = 10,
                 mem_every: int = 64):
        self._fn = fn
        self.profile_name = name
        self.skip_args = tuple(skip_args)
        self.skip_kwargs = tuple(skip_kwargs)
        self.block = block
        self.storm_threshold = int(storm_threshold)
        self.mem_every = int(mem_every)
        self._mem_countdown = int(mem_every)
        self._span_dispatch = name + ".dispatch"
        self._span_wait = name + ".wait"

    @property
    def wrapped(self) -> Callable:
        return self._fn

    @property
    def __wrapped__(self) -> Callable:
        return self._fn

    def __getattr__(self, item: str) -> Any:
        return getattr(self._fn, item)

    def __repr__(self) -> str:
        return f"ProfiledFunction({self.profile_name!r})"

    def _cache_len(self) -> int:
        probe = getattr(self._fn, "_cache_size", None)
        if probe is None:
            return -1
        try:
            return int(probe())
        except Exception:
            return -1

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        prof = get_profiler()
        if not prof.enabled:
            return self._fn(*args, **kwargs)
        sig, h2d = signature_of(args, kwargs, self.skip_args,
                                self.skip_kwargs)
        # AOT cost analysis BEFORE the call: donated buffers are still
        # alive, and its compile events stay out of the timed frame.
        cost = prof.maybe_cost_analysis(self, sig, args, kwargs)
        size0 = self._cache_len()
        frame = {"compile_s": 0.0}
        st = _frames()
        st.append(frame)
        t0 = time.perf_counter()
        try:
            with _child_span(self._span_dispatch):
                out = self._fn(*args, **kwargs)
            if self.block:
                with _child_span(self._span_wait):
                    out = jax.block_until_ready(out)
        finally:
            st.pop()
        step_ms = (time.perf_counter() - t0) * 1_000.0
        size1 = self._cache_len()
        if size0 >= 0:
            compiled = size1 > size0
        else:
            compiled = frame["compile_s"] > 0.0
        prof.record_call(self, sig, compiled=compiled,
                         compile_s=frame["compile_s"], step_ms=step_ms,
                         h2d_bytes=h2d, cost=cost)
        self._mem_countdown -= 1
        if self._mem_countdown <= 0:
            self._mem_countdown = self.mem_every
            try:
                prof.sample_memory()
            except Exception:
                pass
        return out


def wrap(fn: Callable, name: str, **kwargs: Any) -> ProfiledFunction:
    """Sugar: ``_step = runtime_profile.wrap(_step, "engine.step")``."""
    return ProfiledFunction(fn, name, **kwargs)


def profiled_device_get(tree: Any, fn: str = "host") -> Any:
    """``jax.device_get`` with device→host bytes accounted to ``fn``
    (``senweaver_runtime_transfer_bytes_total{direction="d2h"}``)."""
    out = jax.device_get(tree)
    prof = get_profiler()
    if prof.enabled:
        prof.account_transfer(fn, _tree_nbytes(out), direction="d2h")
    return out


# -- process-global profiler ---------------------------------------------

_profiler_lock = threading.Lock()
_profiler: Optional[RuntimeProfiler] = None


def get_profiler() -> RuntimeProfiler:
    global _profiler
    with _profiler_lock:
        if _profiler is None:
            _profiler = RuntimeProfiler()
        return _profiler


def set_profiler(profiler: Optional[RuntimeProfiler]) -> None:
    """Swap the global (None → rebuild lazily). Test isolation hook,
    called from ``obs._reset_for_tests``."""
    global _profiler
    with _profiler_lock:
        _profiler = profiler


def sample_memory() -> Dict[str, Dict[str, Any]]:
    """Module-level convenience: sample HBM/live-buffer watermarks via
    the global profiler."""
    return get_profiler().sample_memory()
