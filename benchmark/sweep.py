#!/usr/bin/env python3
"""The knee of an open-loop mix, found once when the cell is defined:

    python3 benchmark/sweep.py --workload <cell> --rates 3,4,5,6,7,8,9,10 --seconds 30

One process, one engine; each rate runs the mix's fixed-work schedule for
``--seconds`` and the engine is drained between rates. The knee is the
highest rate at which the backlog (submitted - finished) at the end is no
larger than a third of the way in. Prints one JSON line per rate and the
knee; the cell's rate (a share of the knee) is then written into the
traffic file by hand, with this table in ``PERF.md``. Also prints slots in
use second by second, from which the ramp's length is read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from benchmark import serving
    from benchmark.drivers import open_fixed_work
    from benchmark.e2e import itl_p99_ms, ttft_p75_ms
    from benchmark.manifest import Manifest, model_config
    from benchmark.weights import make_weights
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                               ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    man = Manifest(args.workload)
    config = model_config(man.config)
    weights = jax.block_until_ready(make_weights(config, args.seed))
    engine = serving.build_engine(weights, config, man.traffic, args.seed)
    serving.warm_up(engine, man.traffic)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(man.traffic, rate_per_s=rate, ramp_seconds=0.0,
                   tail_seconds=0.0)
        feeder = open_fixed_work.make_feeder(mix, args.seconds, args.seed,
                                             config.vocab_size)
        loop = serving.Loop(engine)
        w = serving.Window(t0=serving.clock())
        feeder.start(w.t0)
        feeder.open_window(w.t0)
        slots, marks = [], {}

        def tick(now, t_end, w=w, feeder=feeder, slots=slots, marks=marks):
            sec = int(now - w.t0)
            if sec >= len(slots):
                done = sum(s.done_t is not None for s in feeder.served)
                slots.append(engine.stats()["slots_active"])
                marks[sec] = len(feeder.served) - done
        loop.run(feeder, w.t0 + args.seconds, w, tick)
        w.t1 = serving.clock()
        w.served = list(feeder.served)
        done = sum(s.done_t is not None for s in w.served)
        third = marks.get(int(args.seconds / 3), 0)
        end = len(w.served) - done
        row = {"rate_per_s": rate, "submitted": len(w.served),
               "finished": done, "backlog_at_third": third,
               "backlog_at_end": end,
               "tokens_per_s": sum(s[2] for s in w.steps) / (w.t1 - w.t0),
               "steps": len(w.steps),
               "ttft_p50_ms": float(np.percentile(ttft_p75_ms.waits_ms(w),
                                                  50)),
               "ttft_p75_ms": ttft_p75_ms.read(w, None),
               "ttft_p90_ms": float(np.percentile(ttft_p75_ms.waits_ms(w),
                                                  90)),
               "itl_p99_ms": itl_p99_ms.read(w, None),
               "slots_by_second": slots}
        print(json.dumps(row), flush=True)
        if end <= max(third, 1):
            knee = rate
        while engine.has_work:            # drain before the next rate
            engine.step()
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
