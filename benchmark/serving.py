"""Driving ``RolloutEngine`` from one thread: construction, warm-up of the
cell's own fused-step shapes, and the step loop every serving driver shares.

The loop has no generator thread. Before each ``engine.step()`` it submits
whatever its feeder says is due; it sleeps only when the engine has no work
and nothing is due. Every token is stamped with the host clock at the return
of the step that produced it.
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional

import jax

from .traffic_gen import Request

clock = time.perf_counter


@dataclasses.dataclass
class Served:
    """One request as the engine served it."""
    req: Request
    rid: int
    due_t: float                    # host clock; == submit_t in closed loops
    submit_t: float
    submit_step: int                # engine steps taken before the submit
    measured: bool
    token_t: List[float] = dataclasses.field(default_factory=list)
    first_step: Optional[int] = None
    done_t: Optional[float] = None
    tokens: Optional[List[int]] = None
    logps: Optional[List[float]] = None


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    steps: List[tuple] = dataclasses.field(default_factory=list)
    served: List[Served] = dataclasses.field(default_factory=list)
    lateness_max_s: float = 0.0
    slept_s: float = 0.0
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    stall_notes: List[str] = dataclasses.field(default_factory=list)


def build_engine(params, config, mix: dict, seed: int, kv_dtype: str = ""):
    """The mix's engine. ``kv_dtype`` (the output check's second control,
    never a benchmark run) switches on the engine's own quantized KV."""
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.engine import EngineConfig
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    e = mix["engine"]
    s = mix["sample"]
    engine = RolloutEngine(
        params, config, num_slots=int(e["num_slots"]),
        max_len=int(e["max_len"]), seed=int(seed) & 0x7FFFFFFF,
        sample=SampleParams(temperature=float(s["temperature"]),
                            top_p=float(s.get("top_p", 1.0))),
        eos_id=None,
        engine_config=EngineConfig(step_tokens=e.get("step_tokens"),
                                   **({"kv_dtype": kv_dtype} if kv_dtype
                                      else {})))
    if engine.kv_layout != "paged" or engine.kv_layout_fallback:
        raise RuntimeError(f"engine left the paged layout: "
                           f"{engine.kv_layout} "
                           f"({engine.kv_layout_fallback})")
    return engine


def warm_up(engine, mix: dict) -> int:
    """Every fused-step shape the mix can meet, through the public API
    alone. The engine buckets the block-table width to powers of two and the
    token width to {num_slots, step_tokens}; one lone request whose prompt
    ends just inside a bucket takes a prefill step and a decode step at that
    table width, so a ladder of such prompts visits each pair once. Returns
    the number of engine steps it took."""
    bs = engine.engine_config.block_size
    longest = int(mix["prompt_len"].get("hi", mix["prompt_len"].get("value"))
                  ) + int(mix["output_len"].get("hi",
                                                mix["output_len"].get("value")))
    top = min(longest + bs, engine.max_len - 3)
    steps, width = 0, 1
    while True:
        n = min(width * bs - bs // 2, top)
        engine.submit([1] * n, max_new_tokens=2)
        while engine.has_work:
            engine.step()
            steps += 1
        if n >= top:
            break
        width *= 2
    if mix.get("group_size", 1) > 1:
        # a prompt that ends inside a block: the followers' first write
        # copies the shared boundary block (one more small program)
        engine.submit_group([1] * (2 * bs + bs // 2), 2, max_new_tokens=2)
        while engine.has_work:
            engine.step()
            steps += 1
    return steps


class Loop:
    """The engine, the requests in flight, and the clock."""

    def __init__(self, engine):
        self.engine = engine
        self.live: Dict[int, Served] = {}
        self.n_steps = 0
        self.lateness_max = 0.0
        self.ctx_sum = 0        # KV tokens held by the rows that decode
        self.in_step_since = None

    def submit(self, req: Request, due_t: float, now: float) -> List[Served]:
        if req.group_size > 1:
            rids = self.engine.submit_group(req.prompt, req.group_size,
                                            max_new_tokens=req.out_len)
        else:
            rids = [self.engine.submit(req.prompt,
                                       max_new_tokens=req.out_len)]
        self.lateness_max = max(self.lateness_max, now - due_t)
        out = [Served(req, rid, due_t, now, self.n_steps, req.measured)
               for rid in rids]
        for s in out:
            self.live[s.rid] = s
        return out

    def step(self, steps: Optional[list]) -> List[Served]:
        """One ``engine.step()``; returns the requests it finished."""
        t_a, ctx_before, cpu_a = clock(), self.ctx_sum, time.process_time()
        self.in_step_since = t_a
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            emitted = self.engine.step()
        t_b = clock()
        self.in_step_since = None
        self.n_steps += 1
        n_tok, finished = 0, []
        for rid, toks in emitted.items():
            s = self.live.get(rid)
            if s is None:
                continue
            if not s.token_t:
                s.first_step = self.n_steps
                self.ctx_sum += len(s.req.prompt)
            s.token_t.extend([t_b] * len(toks))
            n_tok += len(toks)
            self.ctx_sum += len(toks)
            if self.engine.is_done(rid):
                self.ctx_sum -= len(s.req.prompt) + len(s.token_t)
                s.done_t = t_b
                s.tokens = self.engine.result(rid)
                s.logps = self.engine.result_logps(rid)
                del self.live[rid]
                finished.append(s)
        if steps is not None:
            steps.append((t_a, t_b, n_tok, ctx_before,
                          time.process_time() - cpu_a))
        return finished

    def run(self, feeder, t_end: float, window: Optional[Window] = None,
            on_tick: Optional[Callable[[float, float], None]] = None) -> None:
        """Drive until ``t_end``. ``feeder.due(now, loop)`` submits what is
        due and returns the Served it made; ``feeder.finished(served, now,
        loop)`` hears of completions; ``feeder.next_due()`` is when it next
        wants to submit (None: only on a completion)."""
        steps = window.steps if window is not None else None
        while True:
            now = clock()
            if now >= t_end:
                break
            if on_tick is not None:
                on_tick(now, t_end)
            feeder.due(now, self)
            if self.engine.has_work:
                for s in self.step(steps):
                    feeder.finished(s, clock(), self)
                continue
            nxt = feeder.next_due()
            if nxt is None:
                raise RuntimeError("engine idle and the feeder has nothing "
                                   "due: the loop would never end")
            pause = min(nxt, t_end) - clock()
            if pause > 0:
                time.sleep(pause)
                if window is not None:
                    window.slept_s += pause


class StallWatch(threading.Thread):
    """Names a stall: a thread that sleeps, and when one ``engine.step()``
    has lasted over ``after`` seconds notes where the main thread stands
    and the kernel's count of major page faults. It measures nothing; its
    notes go to the log."""

    def __init__(self, loop: Loop, after: float = 0.5):
        super().__init__(daemon=True)
        self.loop, self.after = loop, after
        self.notes: List[str] = []
        self.done = threading.Event()
        self.main = threading.main_thread().ident

    @staticmethod
    def major_faults() -> int:
        try:
            with open("/proc/self/stat") as f:
                return int(f.read().rsplit(")", 1)[1].split()[9])
        except (OSError, ValueError, IndexError):
            return -1

    def run(self):
        seen = None
        while not self.done.wait(0.05):
            since = self.loop.in_step_since
            if since is None or since == seen or clock() - since < self.after:
                continue
            seen = since
            frame = sys._current_frames().get(self.main)
            where = " < ".join(
                f"{f.name}:{f.lineno}" for f in
                reversed(traceback.extract_stack(frame)[-6:])) if frame \
                else "?"
            self.notes.append(f"step running {clock() - since:.2f} s, major "
                              f"faults so far {self.major_faults()}, main "
                              f"thread at {where}")

    def stop(self) -> List[str]:
        self.done.set()
        self.join(timeout=2.0)
        return self.notes + [f"major faults at the end {self.major_faults()}"]


def longest_steps(w: Window, n: int = 3) -> list:
    """The window's longest steps, for the log: (ms, seconds into the
    window, tokens emitted, ms of process CPU time). A stall shows here
    before it shows in a tail."""
    top = sorted(w.steps, key=lambda s: s[0] - s[1])[:n]
    return [(round(1e3 * (b - a), 1), round(a - w.t0, 2), k,
             round(1e3 * cpu, 1)) for a, b, k, _c, cpu in top]


def engine_counters(engine) -> dict:
    keep = ("prefills", "prefill_tokens", "decode_steps", "tokens_emitted",
            "kv_preemptions", "group_prefills", "group_forks",
            "group_degrades", "batched_prefills")
    st = engine.stats()
    return {k: st[k] for k in keep if k in st}


# ---- what every serving driver shares: set-up, window, output check -------

@dataclasses.dataclass
class State:
    weights: object
    engine: object
    loop: Loop
    feeder: object
    counters: dict           # of the set-up, for the per-layer readers


def prepare(ctx, make_feeder) -> State:
    """Everything before the window: weights, engine, the cell's own shapes,
    and the ramp (the same traffic, unmeasured, for ``ramp_seconds``)."""
    from .weights import make_weights
    mix = ctx.manifest.traffic
    t = clock()
    weights = jax.block_until_ready(make_weights(ctx.config, ctx.seed))
    ctx.log(f"weights on device in {clock() - t:.2f} s")
    t = clock()
    engine = build_engine(weights, ctx.config, mix, ctx.seed,
                          ctx.engine_kv_dtype)
    warm = warm_up(engine, mix)
    ctx.log(f"warm-up: {warm} engine steps in {clock() - t:.2f} s")
    feeder = make_feeder(mix, ctx.seconds, ctx.seed, ctx.config.vocab_size)
    loop = Loop(engine)
    t = clock()
    feeder.start(t)
    loop.run(feeder, t + float(mix["ramp_seconds"]))
    st = engine.stats()
    ctx.log(f"ramp: {loop.n_steps} steps in {clock() - t:.2f} s; slots in "
            f"use {st['slots_active']}/{engine.num_slots}, queued "
            f"{st['queue_depth']}")
    return State(weights, engine, loop, feeder,
                 {"warm_steps": warm, "ramp_steps": loop.n_steps})


def window(ctx, state: State, on_tick=None) -> Window:
    w = Window()
    loop = state.loop
    loop.lateness_max = 0.0
    w.stats0 = engine_counters(state.engine)
    watch = StallWatch(loop)
    watch.start()
    w.t0 = clock()
    state.feeder.open_window(w.t0)
    loop.run(state.feeder, w.t0 + ctx.seconds, w, on_tick)
    w.t1 = clock()
    w.stall_notes = watch.stop()
    w.stats1 = engine_counters(state.engine)
    w.lateness_max_s = loop.lateness_max
    w.served = list(state.feeder.served)
    return w


def notes(w: Window) -> List[str]:
    """The window in words, for the log."""
    moved = {k: w.stats1[k] - w.stats0[k] for k in w.stats1}
    return [f"window {w.t1 - w.t0:.3f} s: {len(w.steps)} steps, "
            f"{sum(s[2] for s in w.steps)} tokens, generator lateness max "
            f"{1e3 * w.lateness_max_s:.2f} ms, slept {w.slept_s:.2f} s, "
            f"longest steps (ms, at s, tokens, cpu ms) {longest_steps(w)}, "
            f"counters {moved}"] + [f"stall watch: {n}" for n in w.stall_notes]


def trace_started(state: State) -> int:
    """Prefill tokens so far, when the trace begins."""
    return state.engine.stats()["prefill_tokens"]


def traced_part(record, state: State, tracer) -> None:
    """What the roofline reader needs of the steps the trace covers: tokens
    sampled and KV tokens resident, step by step, and the prefill tokens of
    the traced part as a whole."""
    record.traced_steps = [
        {"decode": n, "sampled": n, "contexts": c}
        for a, _b, n, c, _cpu in record.window.steps
        if a >= tracer.started_at]
    record.traced_prefill_tokens = (
        state.engine.stats()["prefill_tokens"] - tracer.mark)


def release(state: State) -> None:
    """Free the program's device state; the weights stay for the
    reference."""
    state.engine.pool = None
    state.engine.params = None
    state.engine = None
    state.loop.engine = None


def compared(ctx, state: State, w: Window) -> list:
    from . import correct
    mix, limits = ctx.manifest.traffic, ctx.manifest.limits
    finished = [s for s in w.served
                if s.done_t is not None and w.t0 <= s.done_t <= w.t1]
    wrong = [s for s in finished if len(s.tokens) != s.req.out_len
             or len(s.logps) != s.req.out_len]
    attempted = state.feeder.attempted(w)
    failed = [s for s in attempted if s in wrong or not s.token_t]
    out = [correct.Compared("finished_with_wrong_length", float(len(wrong)),
                            0.0)]
    n_pos = int(mix["output_len"].get("hi", mix["output_len"].get("value")))
    out += correct.served_model(state.weights, ctx.manifest.config,
                                [s for s in finished if s not in wrong],
                                limits, n_pos, ctx.control)
    return out, len(attempted), len(failed)
