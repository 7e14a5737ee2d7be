"""The idle ledger (``benchmark/readers/idle_ledger.py``: the yardstick of
``idle_gap_*``, which imports nothing of the program) on spans and device
runs whose answer is known by construction: the parts and the gap do not
depend on the offset between the two clocks, the causal window holds the
offset, and a join that cannot be right returns nothing and says why."""

import types

import pytest

from benchmark.manifest import HERE, load_json
from benchmark.readers import idle_ledger
from benchmark.readers.idle_ledger import PARTS

# the span names the cells' metrics hand the reader
NAMES = load_json(HERE, "layer_metrics",
                  "idle_gap_ms.rollout.json")["args"]["spans"]


def attribute(fused, others, spans):
    return idle_ledger.attribute(fused, others, spans, NAMES)


def unqueued_share(spans):
    return idle_ledger.unqueued_share(spans, "engine.step", "unqueued_ms")


US = 1_000                      # ns


def span(name, start, end, sid, parent=None, **attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 start_ns=int(start), end_ns=int(end),
                                 duration_ms=(end - start) / 1e6,
                                 attrs=attrs)


def build(n=9, offset_ns=0, cow_every=3, state_every=0, other_dev_us=40):
    """``n`` steps on a host clock that reads the device's clock plus
    ``offset_ns``. Step k: plan ``200 + 10k`` us (a copy-on-write span of
    150 us inside it every ``cow_every`` steps, whose device copy runs
    ``other_dev_us``), dispatch ``650 + 5k``, the wrapper's tail 250, the
    run ``9000 + 100k`` starting ``300 + 7k`` after the dispatch's end,
    the fetch returning ``900 + 3k`` after the run's end, emit ``170 + k``,
    the caller ``250 + 2k``. Returns (spans, fused runs, other runs, the
    expected rows in us, start and return latencies in us by step)."""
    spans, fused, others, want = [], [], [], []
    t = 5_000_000_000
    lat = []
    prev = None
    for k in range(n):
        step0 = t
        plan = (200 + 10 * k) * US
        cow = 150 * US if cow_every and k % cow_every == 0 else 0
        state = 60 * US if state_every and k % state_every == 1 else 0
        launch0 = step0 + plan + cow + state
        dispatch_end = launch0 + (650 + 5 * k) * US
        launch1 = dispatch_end + 250 * US
        start_lat = (300 + 7 * k) * US
        dur = (9000 + 100 * k) * US
        run0 = dispatch_end + start_lat            # true (host) time
        return_lat = (900 + 3 * k) * US
        fetch1 = run0 + dur + return_lat
        emit = (170 + k) * US
        step1 = fetch1 + emit
        sid = f"s{k}"
        spans += [
            span("engine.plan", step0, launch0, f"p{k}", sid),
            span("engine.assemble_plan", step0 + 50 * US, launch0 - US,
                 f"a{k}", f"p{k}"),
            span("engine.launch", launch0, launch1, f"l{k}", sid),
            span("engine.fused_step.dispatch", launch0 + 20 * US,
                 dispatch_end, f"d{k}", f"l{k}"),
            span("engine.fetch", launch1 + US, fetch1, f"f{k}", sid),
            span("engine.emit", fetch1 + US, step1 - US, f"e{k}", sid),
        ]
        dev_busy = 0
        if cow:
            c0 = step0 + 60 * US
            spans.append(span("engine.cow_copy", c0, c0 + cow, f"c{k}",
                              f"a{k}", blocks=1, bytes=4096))
            spans.append(span("paged_kv.copy.dispatch", c0 + US,
                              c0 + 30 * US, f"cd{k}", f"c{k}"))
            others.append((c0 + 40 * US - offset_ns, other_dev_us * US))
            dev_busy = other_dev_us * US
        if state:
            z0 = launch0 - state - US
            spans.append(span("engine.state_copy", z0, z0 + state, f"z{k}",
                              f"p{k}", src=1, dst=2))
        spans.append(span("engine.step", step0, step1, sid, step=k,
                          unqueued_ms=0.0))
        fused.append((run0 - offset_ns, dur))
        lat.append((start_lat / US, return_lat / US))
        if prev is not None:
            p_run_end, p_fetch1, p_step1, p_emit = prev
            chain = (p_emit + (step0 - p_step1) + plan + cow + state
                     + dispatch_end - launch0)
            gap = run0 - p_run_end - dev_busy
            want.append({"gap": gap / US, "emit": p_emit / US,
                         "caller": (step0 - p_step1) / US,
                         "plan": plan / US, "copies": (cow + state) / US,
                         "dispatch": (dispatch_end - launch0) / US,
                         "runtime": (gap - chain) / US})
        prev = (run0 + dur, fetch1, step1, emit)
        t = step1 + (250 + 2 * k) * US
    # what an earlier request left in the tracer, and a phase span
    spans.append(span("request.decode", 1, 2, "r0"))
    return spans, fused, others, want, lat


@pytest.mark.parametrize("offset_ns", [0, 420_000, -3_700_000,
                                       9_000_000_000_000])
def test_parts_and_gap_do_not_move_with_the_offset_and_the_window_holds_it(
        offset_ns):
    spans, fused, others, want, lat = build(offset_ns=offset_ns,
                                            state_every=4)
    led, why = attribute(fused, others, spans)
    assert why == "" and led.pairs == 9 and led.floored == 0
    # the first and the last pair are dropped: gaps before runs 2 .. 7
    assert len(led.steps) == 6
    for got, exp in zip(led.steps, want[1:-1]):
        for key in ("gap",) + PARTS:
            assert got[key] == pytest.approx(exp[key] / 1e3, abs=1e-9), key
        assert sum(got[p] for p in PARTS) == pytest.approx(got["gap"])
    lo, hi = led.offset_window_ms
    assert lo <= offset_ns / 1e6 <= hi
    # as wide as the smallest dispatch-plus-start latency and the smallest
    # return latency left it: step 0 has both (650 + 300 us, 900 us)
    assert hi - lo == pytest.approx((650 + 300 + 900) / 1e3)
    # the split: the sum is the same at both ends, and is the runtime
    s_lo, r_lo = led.split_at_lower
    s_hi, r_hi = led.split_at_upper
    assert s_lo + r_lo == pytest.approx(s_hi + r_hi)
    assert s_lo + r_lo == pytest.approx(led.mean("runtime")
                                        + led.mean("other"))
    assert led.mean("other") == pytest.approx(2 * 0.040 / 6)
    # at the true offset start and return are the constructed means,
    # and the two ends bracket them
    starts = [lat[k][0] for k in range(2, 8)]
    returns = [lat[k - 1][1] for k in range(2, 8)]
    mean = lambda v: sum(v) / len(v) / 1e3
    assert s_lo <= mean(starts) <= s_hi and r_hi <= mean(returns) <= r_lo
    assert s_lo - lo + offset_ns / 1e6 == pytest.approx(mean(starts))


def test_other_programs_device_time_is_taken_out_of_the_gap():
    spans, fused, _others, want, _ = build(other_dev_us=0)
    with_busy = build(other_dev_us=40)
    led0, _ = attribute(fused, [], spans)
    led1, _ = attribute(with_busy[1], with_busy[2], with_busy[0])
    # steps 3 and 6 copy a block: rows 1 and 4 of the kept six
    for i, (a, b) in enumerate(zip(led0.steps, led1.steps)):
        less = 0.040 if i in (1, 4) else 0.0
        assert b["gap"] == pytest.approx(a["gap"] - less)
        assert b["runtime"] == pytest.approx(a["runtime"] - less)
        assert b["copies"] == a["copies"] == (0.150 if less else 0.0)


def test_the_means_add_up_and_the_summary_names_every_part():
    spans, fused, others, _want, _ = build(n=40)
    led, _ = attribute(fused, others, spans)
    s = led.summary()
    assert s["pairs"] == 40 and s["steps"] == 37 and s["floored"] == 0
    assert sum(s[p + "_ms"] for p in PARTS) == pytest.approx(s["gap_ms"])
    assert led.mean("gap") == s["gap_ms"]
    # every gap of the traced part, the dropped first and last included:
    # 39 gaps between 40 runs, known by construction
    all_gaps = sum(b[0] - (a[0] + a[1]) for a, b in zip(fused, fused[1:]))
    assert s["gaps_all_ms"] == pytest.approx(
        (all_gaps - sum(d for _s, d in others if _s > fused[0][0])) / 1e6)
    assert s["gaps_all_ms"] > 37 * s["gap_ms"]


def test_a_chain_longer_than_its_gap_is_floored_and_counted():
    spans, fused, others, _want, _ = build()
    # run 4 starts 1.5 ms earlier: before its own launch began
    fused[4] = (fused[4][0] - 1_500 * US, fused[4][1] + 1_500 * US)
    led, why = attribute(fused, others, spans)
    assert why == "" and led.floored == 1
    assert led.steps[2]["runtime"] == 0.0


def test_a_first_run_the_window_cut_off_leaves_its_step_unpaired():
    """The traced window begins at a host event and the runs are on the
    device's clock: the first step's run can lie just before it. Anchored
    at the last step, the ledger leaves that one step out, says so, and
    the rows are the whole ledger's from its second on."""
    spans, fused, others, _want, _ = build()
    whole, _ = attribute(fused, others, spans)
    led, why = attribute(fused[1:], others, spans)
    assert why == "" and (led.pairs, led.unpaired_first) == (8, 1)
    assert whole.unpaired_first == 0
    assert led.steps == whole.steps[1:]
    assert led.summary()["unpaired_first"] == 1
    # one pair fewer bounds the offset no tighter
    lo, hi = led.offset_window_ms
    assert lo <= whole.offset_window_ms[0] <= 0.0
    assert 0.0 <= whole.offset_window_ms[1] <= hi


@pytest.mark.parametrize("mutate,says", [
    # a run missing in the middle: anchored at the last, every earlier
    # pair is a whole step off, and no offset is causal for all
    (lambda sp, fu: fu.pop(3), "no offset between the clocks"),
    (lambda sp, fu: (fu.pop(0), fu.pop(0)),
     "9 engine.step spans with a launch and a fetch, 7 runs"),
    (lambda sp, fu: fu.append((fu[-1][0] + 20_000 * US, 9_000 * US)),
     "9 engine.step spans with a launch and a fetch, 10 runs"),
    (lambda sp, fu: [sp.remove(s) for s in list(sp)
                     if s.span_id in ("l5", "d5")],
     "8 engine.step spans with a launch and a fetch, 9 runs"),
    # equal counts, one step dropped and one run too many elsewhere: the
    # shifted pairs leave no offset that is causal for all
    (lambda sp, fu: ([sp.remove(s) for s in list(sp)
                      if s.span_id in ("l2", "d2")], fu.pop(7)),
     "no offset between the clocks"),
    (lambda sp, fu: fu.__setitem__(4, (fu[4][0], 30_000 * US)),
     "launch-to-fetch time"),
], ids=["dropped-run", "two-runs-cut", "extra-run", "step-without-launch",
        "shifted", "run-longer-than-its-step"])
def test_a_join_that_cannot_be_right_returns_nothing_and_names_why(
        mutate, says):
    spans, fused, others, _want, _ = build()
    mutate(spans, fused)
    led, why = attribute(fused, others, spans)
    assert led is None and says in why


def test_too_few_steps_to_keep_one():
    spans, fused, others, _want, _ = build(n=3)
    led, why = attribute(fused, others, spans)
    assert led is None and "3 paired steps" in why
    assert attribute([], [], [])[0] is None


def test_a_launch_without_its_dispatch_child_ends_at_the_launch():
    """A disabled runtime profiler wraps nothing: no ``.dispatch``
    span. The launch's own end stands for it."""
    spans, fused, others, want, _ = build()
    spans = [s for s in spans
             if s.name != "engine.fused_step.dispatch"]
    led, why = attribute(fused, others, spans)
    assert why == ""
    for got, exp in zip(led.steps, want[1:-1]):
        assert got["dispatch"] == pytest.approx(exp["dispatch"] / 1e3
                                                + 0.250)


def test_unqueued_share_reads_the_attr_over_the_steps_span():
    steps = [span("engine.step", 0, 10_000 * US, "a", unqueued_ms=45.0),
             span("engine.step", 11_000 * US, 20_000 * US, "b",
                  unqueued_ms=1.5),
             span("engine.step", 21_000 * US, 30_000 * US, "c",
                  unqueued_ms=1.0),
             span("engine.step", 31_000 * US, 40_000 * US, "d"),
             span("engine.plan", 0, 1, "p", "a", unqueued_ms=99.0)]
    # the first step's reading is what came before the window: left out
    assert unqueued_share(steps) == pytest.approx(100 * 2.5 / 30.0)
    assert unqueued_share(steps[::-1]) == unqueued_share(steps)
    assert unqueued_share(steps[3:]) is None
    assert unqueued_share(steps[:1]) is None
    assert unqueued_share([]) is None
