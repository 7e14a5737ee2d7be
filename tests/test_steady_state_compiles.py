"""Zero new compiles after warm-up, on the hot functions the chip runs.

Each case builds a tiny workload, runs it once (compiles land there),
then runs it again and reads the ``obs/runtime_profile`` ledger: the
steady pass may add no compile to the case's function(s). On the chip a
violation reads ``window_compiles`` > 0 and ``correct: false`` in every
benchmark cell. The workloads are greedy on fixed prompts; nothing here
reads a clock.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import senweaver_ide_tpu.obs as obs
from senweaver_ide_tpu.models import init_params, tiny_test
from senweaver_ide_tpu.obs.runtime_profile import get_profiler
from senweaver_ide_tpu.rollout import (AdapterPool, AdapterPoolConfig,
                                       EngineConfig, RolloutEngine)
from senweaver_ide_tpu.rollout.sampler import SampleParams

GREEDY = SampleParams(temperature=0.0, top_k=0, top_p=1.0)
FUSED = ("engine.fused_step",)
GRPO = ("trainer.grpo_step",)


@pytest.fixture(scope="module")
def tiny():
    config = tiny_test()
    return config, jax.block_until_ready(
        init_params(config, jax.random.PRNGKey(0)))


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


def _compiles(fns) -> int:
    """Compiles the ledger holds for ``fns``; ``None`` is every fn."""
    ledger = get_profiler().ledger()
    names = ledger if fns is None else fns
    return sum(int(ledger[n]["compiles"]) for n in names if n in ledger)


def _steady_compiles(run, fns, iters: int) -> int:
    """Compiles that ``iters`` runs add after one warm-up run; every
    run must return what the warm-up returned. A first steady pass of a
    jitted function can still meet a cold signature: it is run once
    more, and a second leak is the result. Host-only code (``fns`` is
    ``None``) has no such excuse and gets one pass."""
    warm = run()                            # compiles land here
    for attempt in range(1 if fns is None else 2):
        before = _compiles(fns)
        for _ in range(iters):
            assert run() == warm
        leaked = _compiles(fns) - before
        if leaked == 0:
            break
        if attempt == 0 and fns is not None:
            warnings.warn(f"steady pass of {fns} compiled {leaked} "
                          "time(s); running it once more")
    return leaked


def _prompts(n: int, length: int):
    return [[(i * 7 + j) % 200 + 2 for j in range(length)]
            for i in range(n)]


def _paged(**kw) -> EngineConfig:
    return EngineConfig(kv_layout="paged", **kw)


def _trainer(config, params):
    from senweaver_ide_tpu.training.trainer import (TrainState,
                                                    make_optimizer)
    opt = make_optimizer()
    return opt, TrainState(params=params,
                           opt_state=jax.jit(opt.init)(params),
                           step=jnp.zeros((), jnp.int32), opt=opt)


def _decode(config, params, draft=None):
    def run():
        eng = RolloutEngine(params, config, num_slots=4, max_len=128,
                            sample=GREEDY, engine_config=_paged())
        if draft is not None:
            eng.enable_speculation(*draft, depth=4)
        for p in _prompts(4, 16):
            eng.submit(p, max_new_tokens=24)
        out = eng.run()
        assert [len(t) for t in out.values()] == [24] * 4
        return out
    return run


def _engine_decode(config, params):
    """Paged fused-step decode through RolloutEngine."""
    return _decode(config, params), FUSED, 3


def _spec_decode(config, params):
    """The same workload with a depth-4 draft fused into the step."""
    draft_cfg = dataclasses.replace(config, num_layers=2,
                                    name="tiny-draft")
    draft = jax.block_until_ready(
        init_params(draft_cfg, jax.random.PRNGKey(1)))
    return (_decode(config, params, (draft, draft_cfg)),
            FUSED + ("engine.spec_propose",), 3)


def _pressured(config, params, **cfg):
    """Six prompts on one 16-token prefix, ~2x over a 10-block pool of
    two slots; returns the drained engine, the prefix id, the tokens."""
    prefix = [(j * 11) % 200 + 2 for j in range(16)]
    eng = RolloutEngine(
        params, config, num_slots=2, max_len=128, sample=GREEDY,
        engine_config=_paged(block_size=4, num_blocks=10, **cfg))
    pid = eng.register_prefix(prefix)
    for tail in _prompts(6, 4):
        eng.submit(prefix + tail, max_new_tokens=12, prefix_id=pid)
    return eng, pid, eng.run()


def _kv_pressure(config, params):
    """The pressured workload with the host tier on: scored eviction,
    swap-out / restore and preemption replay all ride the fused step,
    and the shared prefix survives them."""
    def run():
        eng, pid, out = _pressured(config, params)
        eng.release_prefix(pid)
        eng._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _kv_quant(config, params):
    """The pressured workload on an int8 pool: the scale scatter, COW,
    preemption replay and prefix grafts ride the one fused-step
    signature."""
    def run():
        eng, pid, out = _pressured(config, params, kv_dtype="int8",
                                   host_tier=False)
        if pid in eng._prefixes:
            eng.release_prefix(pid)
        eng._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _migration(config, params):
    """Checkpoint three mid-flight decodes off engine A, install them
    on engine B, finish them there, release the source copies."""
    def run():
        a, b = (RolloutEngine(params, config, num_slots=4, max_len=128,
                              sample=GREEDY, engine_config=_paged())
                for _ in range(2))
        rids = [a.submit(p, max_new_tokens=16) for p in _prompts(3, 12)]
        for _ in range(6):
            a.step()
        for rid in rids:
            b.restore_request(a.checkpoint_request(rid))
            a.release_request(rid)
        out = b.run()
        assert [len(t) for t in out.values()] == [16] * 3
        a._alloc.check_leaks()
        b._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _multi_lora(config, params):
    """Four tenants across both rank rungs ride one pool engine's fused
    step; each run rebuilds the pool and re-acquires every slot, so
    tenant churn must land on warm signatures."""
    from senweaver_ide_tpu.training.lora import init_lora

    loras = {}
    for i in range(4):
        lora = init_lora(config, jax.random.PRNGKey(10 + i),
                         rank=8 if i % 2 else 16)
        for k, leaf in lora["layers"].items():
            if k.endswith("_lora_b"):
                lora["layers"][k] = jax.random.normal(
                    jax.random.PRNGKey(50 + i), leaf.shape,
                    leaf.dtype) * 0.05
        loras[f"tenant-{i}"] = lora

    def run():
        pool = AdapterPool(config, AdapterPoolConfig(slots_per_rank=2))
        eng = RolloutEngine(params, config, num_slots=4, max_len=128,
                            sample=GREEDY, adapter_pool=pool,
                            engine_config=_paged())
        for name, lora in loras.items():
            eng.publish_adapter(name, lora)
        for p, name in zip(_prompts(4, 16), loras):
            eng.submit(p, max_new_tokens=24, adapter_id=name)
        out = eng.run()
        assert [len(t) for t in out.values()] == [24] * 4
        eng._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _group_rollout(config, params):
    """One G=8 group decodes off a single donor prefill; a silent
    degrade to per-member prefills fails the prefill count."""
    prompt = [(j * 11) % 200 + 2 for j in range(24)]

    def run():
        eng = RolloutEngine(params, config, num_slots=8, max_len=128,
                            sample=GREEDY,
                            engine_config=_paged(block_size=4))
        eng.submit_group(prompt, 8, max_new_tokens=16)
        out = eng.run()
        assert [len(t) for t in out.values()] == [16] * 8
        assert eng.stats()["prefills"] == 1
        eng._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _state_group_rollout(config, params):
    """The same group on a model with recurrent state (a state-space
    mixer in every block): the donor's prefill stops before the prompt's
    last token, one snapshot and seven installs of its state rows are ONE
    program (``paged_kv.copy_state``), and every member feeds the last
    token through the fused step's two widths."""
    from senweaver_ide_tpu.models.config import tiny_falcon_h1_test
    hybrid = tiny_falcon_h1_test()
    weights = jax.block_until_ready(
        init_params(hybrid, jax.random.PRNGKey(0)))
    prompt = [(j * 11) % 200 + 2 for j in range(24)]

    def run():
        eng = RolloutEngine(weights, hybrid, num_slots=8, max_len=128,
                            sample=GREEDY,
                            engine_config=_paged(block_size=4))
        eng.submit_group(prompt, 8, max_new_tokens=16)
        out = eng.run()
        assert [len(t) for t in out.values()] == [16] * 8
        st = eng.stats()
        assert (st["prefills"], st["group_forks"]) == (1, 7)
        eng._alloc.check_leaks()
        return out
    return run, FUSED + ("paged_kv.copy_state",), 3


def _share_group_rollout(config, params):
    """Two groups of four on four rows of a model that holds a share of
    its router's experts on a shortcut beside a double block: the second
    group waits in the queue, so the engine runs a step ahead, and every
    step carries four routing counts behind its tokens, at both widths."""
    from senweaver_ide_tpu.models.config import tiny_longcat_flash_test
    share = tiny_longcat_flash_test()
    weights = jax.block_until_ready(
        init_params(share, jax.random.PRNGKey(0)))
    prompt = [(j * 11) % 200 + 2 for j in range(24)]

    def run():
        eng = RolloutEngine(weights, share, num_slots=4, max_len=128,
                            sample=GREEDY,
                            engine_config=_paged(block_size=4))
        for shift in (0, 1):
            eng.submit_group(prompt[shift:], 4, max_new_tokens=16)
        out = eng.run()
        assert [len(t) for t in out.values()] == [16] * 8
        st = eng.stats()
        assert (st["prefills"], st["group_forks"]) == (2, 6)
        eng._alloc.check_leaks()
        return out
    return run, FUSED, 3


def _train_step(config, params):
    """One GRPO update via training.trainer.train_step."""
    from senweaver_ide_tpu.training.trainer import train_step

    opt, state = _trainer(config, params)
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (4, 64), 0, config.vocab_size,
                                dtype=jnp.int32)
    mask = jnp.ones((4, 64), jnp.bool_)
    rewards = jax.random.normal(key, (4,), jnp.float32)
    group_ids = jnp.arange(4, dtype=jnp.int32) // 2
    holder = {"state": state}

    def run():
        st, _ = train_step(holder["state"], config, None, tokens, mask,
                           rewards, group_ids, optimizer=opt)
        jax.block_until_ready(st.params)
        holder["state"] = st
    return run, GRPO, 3


def _streaming_grpo(config, params):
    """The streaming learner's loop: bounded-queue intake with dedup
    and the staleness filter, batch assembly from recorded behavior
    logps, the importance-corrected step. Per-round group churn must
    land on a warm train signature."""
    from senweaver_ide_tpu.training.experience import (
        ExperienceQueue, StreamedEpisode, StreamingTrainerAdapter)

    opt, state = _trainer(config, params)
    adapter = StreamingTrainerAdapter(state, config, None, optimizer=opt,
                                      max_len=32)
    queue = ExperienceQueue(group_size=8, max_staleness=64)
    rounds = {"n": 0}

    def run():
        r = rounds["n"] = rounds["n"] + 1
        eps = [StreamedEpisode(
            episode_id=f"pg/r{r}/i{i}", group_key=f"pg/r{r}",
            prompt_ids=[(i * 7 + j) % 200 + 2 for j in range(8)],
            completion_ids=[(i + j) % 200 + 2 for j in range(4)],
            reward=float(i % 3) - 1.0, epoch=1, version=r,
            behavior_logp=[-0.5, -0.25, -0.5, -0.25])
            for i in range(8)]
        queue.offer_many(eps, current_version=r)
        batch = queue.take_batch(current_version=r)
        assert batch is not None
        adapter.train_on_batch(batch)
        adapter.note_published(r)
        jax.block_until_ready(adapter.params)
    return run, GRPO, 3


def _reward_head(config, params):
    """The jitted batch reward scorer."""
    from senweaver_ide_tpu.rewards.head import reward_head_batch
    from senweaver_ide_tpu.traces.features import N_FEATURES

    feats = jnp.asarray(
        np.random.default_rng(0).uniform(0, 5, (32, N_FEATURES)),
        dtype=jnp.float32)

    def run():
        reward_head_batch(feats)
    return run, ("reward.head_batch",), 5


def _fleet_scrape(config, params):
    """Three peers' registries keep moving, the federator delta-scrapes
    them over loopback rpc, the store ingests and rolls up, the alert
    manager sweeps the stock rules. Host Python by contract: the WHOLE
    ledger stays frozen."""
    from senweaver_ide_tpu.obs import MetricsScrapeMixin
    from senweaver_ide_tpu.serve.remote_server import RpcHandlerBase
    from senweaver_ide_tpu.serve.rpc import LoopbackTransport

    class _ObsScrapeHandler(MetricsScrapeMixin, RpcHandlerBase):
        mutating_methods = frozenset({"scrape"})
        span_service = "obs"

    clock = {"t": 0.0, "n": 0}

    def now() -> float:
        return clock["t"]

    journal = obs.EventJournal(clock=now)
    store = obs.FleetMetricsStore(clock=now)
    peers = {}
    instruments = []
    for i in range(3):
        reg = obs.MetricsRegistry()
        h = _ObsScrapeHandler()
        h.scrape_peer = f"peer-{i}"
        h.scrape_registry = reg
        h.scrape_journal = obs.EventJournal(clock=now, registry=reg)
        h.scrape_clock = now
        peers[f"peer-{i}"] = LoopbackTransport(h, target=f"peer-{i}")
        instruments.append((
            reg.gauge("senweaver_kv_pressure", ""),
            reg.counter("senweaver_serve_slo_requests_total", "",
                        labelnames=("priority",)),
            reg.counter("senweaver_serve_slo_violations_total", "",
                        labelnames=("priority",)),
            reg.histogram("senweaver_learner_episode_staleness", "",
                          buckets=(1.0, 2.0, 4.0, 8.0))))
    fed = obs.MetricsFederator(store, peers, clock=now,
                               journal=journal, interval_s=0.0)
    mgr = obs.AlertManager(store, obs.default_alert_rules(),
                           clock=now, journal=journal)

    def run():
        n = clock["n"] = clock["n"] + 1
        clock["t"] += 1.0
        for j, (kv, reqs, viols, staleness) in enumerate(instruments):
            kv.set(0.3 + 0.05 * ((n + j) % 5))
            reqs.inc(4, priority="interactive")
            if (n + j) % 7 == 0:
                viols.inc(priority="interactive")
            staleness.observe(float((n + j) % 4))
        fed.scrape_once(now())
        mgr.evaluate(now())
    return run, None, 50


CASES = {
    "engine_decode": _engine_decode,
    "spec_decode": _spec_decode,
    "kv_pressure": _kv_pressure,
    "kv_quant": _kv_quant,
    "migration": _migration,
    "multi_lora": _multi_lora,
    "group_rollout": _group_rollout,
    "state_group_rollout": _state_group_rollout,
    "share_group_rollout": _share_group_rollout,
    "train_step": _train_step,
    "streaming_grpo": _streaming_grpo,
    "reward_head": _reward_head,
    "fleet_scrape": _fleet_scrape,
}


@pytest.mark.parametrize("case", list(CASES))
def test_steady_pass_compiles_nothing(case, tiny):
    run, fns, iters = CASES[case](*tiny)
    assert _steady_compiles(run, fns, iters) == 0
