"""The config-ladder capstone: a CLOSED GRPO loop on the real stack —
RolloutSession over the continuous-batching engine (tiny model, CPU),
trace rewards, grouped trajectories, one clipped-objective update."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import get_config
from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
from senweaver_ide_tpu.rollout import (EnginePolicyClient, RolloutEngine,
                                       RolloutSession)
from senweaver_ide_tpu.training import (Trajectory, TrajectoryDataset,
                                        grpo_round, make_batch,
                                        make_train_state)


# ---- data pipeline ----

def test_make_batch_masks_completions_only():
    trajs = [Trajectory([1, 2, 3], [4, 5], reward=1.0, group_id=0),
             Trajectory([1], [6, 7, 8, 9], reward=-1.0, group_id=0)]
    tokens, mask, rewards, gids = make_batch(trajs, pad_id=0)
    assert tokens.shape == (2, 32)            # bucket minimum
    np.testing.assert_array_equal(tokens[0, :5], [1, 2, 3, 4, 5])
    assert mask[0, :3].sum() == 0 and mask[0, 3:5].all()
    assert not mask[0, 5:].any()
    assert rewards.tolist() == [1.0, -1.0]


def test_make_batch_overlong_keeps_completion_tail():
    trajs = [Trajectory(list(range(100)), [7] * 10, reward=0.5,
                        group_id=0)]
    tokens, mask, _, _ = make_batch(trajs, pad_id=0, max_len=64)
    assert tokens.shape[1] == 64
    assert mask[0].sum() == 10                # all completion kept
    assert (tokens[0, -10:] == 7).all()


def test_dataset_deterministic_resume():
    trajs = [Trajectory([i], [i], reward=float(i), group_id=i)
             for i in range(16)]
    d1 = TrajectoryDataset(trajs, batch_size=4, seed=7)
    seq1 = [tuple(t.group_id for t in d1.batch_at(c)) for c in range(8)]
    d2 = TrajectoryDataset(trajs, batch_size=4, seed=7)
    d2.cursor = 5
    assert tuple(t.group_id for t in d2.batch_at(5)) == seq1[5]


# ---- closed loop ----

@pytest.fixture(scope="module")
def tiny_stack():
    config = get_config("tiny-test")
    state = make_train_state(config, jax.random.PRNGKey(0), None,
                             learning_rate=1e-3)
    return config, state


def test_closed_grpo_loop(tmp_path, tiny_stack):
    config, state = tiny_stack
    tok = ByteTokenizer()
    made = []

    def make_session():
        engine = RolloutEngine(state.params, config, num_slots=2,
                               max_len=4096, eos_id=tok.eos_id,
                               seed=len(made))
        client = EnginePolicyClient(engine, tok, model_name="tiny-test",
                                    default_max_new_tokens=8,
                                    record_calls=True)
        # Lean prompt: byte-level ids make the full tool grammar ~7k
        # tokens; the closed-loop contract doesn't need it.
        s = RolloutSession(client, str(tmp_path / f"ws{len(made)}"),
                           include_tool_definitions=False)
        made.append(s)
        return s

    # Reward override creates within-group variance (a random tiny model
    # gives uniform trace rewards, which would zero the advantages).
    def reward(task_idx, g, session):
        return 1.0 if g % 2 == 0 else -1.0

    out = grpo_round(state, config, None, make_session,
                     ["task A", "task B"], group_size=2,
                     pad_id=tok.pad_id, max_len=2048,
                     reward_override=reward)
    assert len(out.episodes) == 4
    assert all(e.n_calls >= 1 for e in out.episodes)
    assert len(out.trajectories) >= 4
    assert np.isfinite(out.metrics["loss"])
    assert out.metrics["grad_norm"] > 0
    assert int(out.state.step) == int(state.step) + 1
    # Params actually moved.
    before = jax.tree_util.tree_leaves(state.params)[0]
    after = jax.tree_util.tree_leaves(out.state.params)[0]
    assert not jnp.allclose(before, after)


# ---- sample-time behavior logps ----

def test_engine_logps_match_recompute(tiny_stack):
    """Recorded sample-time logps must equal a post-hoc forward's
    token_logprobs over the same sequence (fp32 parity config)."""
    config, state = tiny_stack
    from senweaver_ide_tpu.models.transformer import forward
    from senweaver_ide_tpu.rollout.engine import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    from senweaver_ide_tpu.training.grpo import token_logprobs

    eng = RolloutEngine(state.params, config, num_slots=1, max_len=64,
                        sample=SampleParams(temperature=0.8, top_k=0,
                                            top_p=0.0), seed=3)
    prompt = [5, 9, 2, 7]
    rid = eng.submit(prompt, max_new_tokens=6)
    out = eng.run()[rid]
    logps = eng.result_logps(rid)
    assert len(logps) == len(out)

    seq = jnp.asarray([prompt + out], jnp.int32)
    logits, _ = forward(state.params, config, seq[:, :-1])
    want = token_logprobs(logits, seq[:, 1:])[0, len(prompt) - 1:]
    np.testing.assert_allclose(np.asarray(logps), np.asarray(want),
                               atol=2e-4)


def test_make_batch_logps_alignment():
    from senweaver_ide_tpu.training import Trajectory, make_batch
    from senweaver_ide_tpu.training.data import make_batch_logps

    trajs = [Trajectory([1, 2, 3], [4, 5], reward=1.0, group_id=0,
                        behavior_logp=[-0.5, -0.7]),
             Trajectory([9], [8, 7, 6], reward=0.0, group_id=1,
                        behavior_logp=[-0.1, -0.2, -0.3])]
    tokens, mask, _, _ = make_batch(trajs, pad_id=0)
    old = make_batch_logps(trajs, tokens, mask)
    # row 0: completion at seq pos 3,4 → target idx 2,3
    np.testing.assert_allclose(old[0, 2:4], [-0.5, -0.7])
    assert old[0, :2].sum() == 0 and old[0, 4:].sum() == 0
    # row 1: completion at pos 1,2,3 → target idx 0,1,2
    np.testing.assert_allclose(old[1, :3], [-0.1, -0.2, -0.3])

    # any trajectory without logps disables the batch
    trajs[1].behavior_logp = None
    assert make_batch_logps(trajs, tokens, mask) is None


def test_grpo_round_uses_recorded_logps(tmp_path, tiny_stack):
    """End-to-end: a round over the engine trains with exact recorded
    ratios — on-policy, so ratio_mean must sit at 1."""
    config, state = tiny_stack
    tok = ByteTokenizer()
    made = []

    def make_session():
        engine = RolloutEngine(state.params, config, num_slots=2,
                               max_len=4096, eos_id=tok.eos_id,
                               seed=10 + len(made))
        client = EnginePolicyClient(engine, tok, model_name="tiny-test",
                                    default_max_new_tokens=6,
                                    record_calls=True)
        s = RolloutSession(client, str(tmp_path / f"lp{len(made)}"),
                           include_tool_definitions=False)
        made.append(s)
        return s

    def reward(task_idx, g, session):
        return 1.0 if g % 2 == 0 else -1.0

    out = grpo_round(state, config, None, make_session, ["task"],
                     group_size=2, pad_id=tok.pad_id, max_len=2048,
                     reward_override=reward)
    assert all(t.behavior_logp is not None for t in out.trajectories)
    assert np.isfinite(out.metrics["loss"])
    np.testing.assert_allclose(out.metrics["ratio_mean"], 1.0, atol=1e-3)


def test_grpo_round_multi_epoch(tmp_path, tiny_stack):
    """ppo_epochs=3 re-steps the same batch against frozen behavior
    logps: 3 optimizer steps, clipping active, finite metrics."""
    config, state = tiny_stack
    tok = ByteTokenizer()
    made = []

    def make_session():
        engine = RolloutEngine(state.params, config, num_slots=2,
                               max_len=4096, eos_id=tok.eos_id,
                               seed=50 + len(made))
        client = EnginePolicyClient(engine, tok, default_max_new_tokens=6,
                                    record_calls=True)
        s = RolloutSession(client, str(tmp_path / f"ep{len(made)}"),
                           include_tool_definitions=False)
        made.append(s)
        return s

    out = grpo_round(state, config, None, make_session, ["t"],
                     group_size=2, pad_id=tok.pad_id, max_len=2048,
                     ppo_epochs=3,
                     reward_override=lambda ti, g, s: float(g % 2) * 2 - 1)
    assert int(out.state.step) == int(state.step) + 3
    assert np.isfinite(out.metrics["loss"])
    # after ≥1 update the policy moved: epoch-3 ratios are off 1
    assert abs(out.metrics["ratio_mean"] - 1.0) > 1e-6


def test_grpo_round_captures_engine_stats(tmp_path, tiny_stack):
    """grpo_round(engine=...) surfaces serving counters in the metrics
    capture; async ppo_epochs multiplies update steps."""
    from senweaver_ide_tpu.services.metrics import MetricsService

    config, state = tiny_stack
    tok = ByteTokenizer()
    shared = RolloutEngine(state.params, config, num_slots=2,
                           max_len=4096, eos_id=tok.eos_id, seed=77)
    made = []

    def make_session():
        client = EnginePolicyClient(shared, tok, default_max_new_tokens=6,
                                    record_calls=True)
        s = RolloutSession(client, str(tmp_path / f"st{len(made)}"),
                           include_tool_definitions=False)
        made.append(s)
        return s

    captured = []
    metrics = MetricsService(jsonl_path=str(tmp_path / "m.jsonl"))
    metrics.capture = lambda ev, props: captured.append((ev, props))
    out = grpo_round(state, config, None, make_session, ["t"],
                     group_size=2, pad_id=tok.pad_id, max_len=2048,
                     metrics_service=metrics, engine=shared,
                     reward_override=lambda ti, g, s: float(g) - 0.5)
    done = [p for ev, p in captured if ev == "GRPO Round Done"]
    assert done and done[0]["engine_tokens_emitted"] > 0
    assert done[0]["engine_prefill_tokens"] > 0


def test_collect_crash_drains_inflight_sessions():
    """Without a resilience config the historical semantics hold — the
    first episode error raises out of collection — but only AFTER every
    in-flight episode finished and closed its session: leaked worker
    threads must not keep stepping an engine the caller tears down."""
    import threading
    import time
    import types

    from senweaver_ide_tpu.training.rl_loop import \
        collect_group_trajectories

    made = []
    lock = threading.Lock()
    fail_next = [True]

    class _Session:
        def __init__(self, fail):
            self.client = types.SimpleNamespace(call_log=[])
            self.closed = False
            self.fail = fail
            made.append(self)

        def run_turn(self, task):
            if self.fail:
                raise RuntimeError("boom")
            time.sleep(0.2)
            self.client.call_log.append(([1, 2], [3]))
            return types.SimpleNamespace(
                trace=None, loop=types.SimpleNamespace(steps=1))

        def close(self):
            self.closed = True

    def make_session():
        with lock:
            fail = fail_next[0]
            fail_next[0] = False
        return _Session(fail)

    with pytest.raises(RuntimeError, match="boom"):
        collect_group_trajectories(make_session, ["a", "b"],
                                   group_size=2, max_parallel=4)
    assert made and all(s.closed for s in made)


def test_train_step_uses_state_optimizer():
    """Regression (r3): train_step must apply updates with the SAME
    transformation whose .init built state.opt_state. The r2 code fell
    back to a module-level lr-1e-5 default whenever the caller didn't
    re-pass the optimizer — silently stepping ~1000x slower than the
    make_train_state(learning_rate=...) the caller asked for."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.training import make_train_state, train_step

    cfg = get_config("tiny-test")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 512)
    mask = jnp.ones((4, 16), jnp.bool_)
    rewards = jnp.asarray([1.0, -1.0, 0.5, -0.5])
    gids = jnp.zeros((4,), jnp.int32)

    def delta(lr):
        st = make_train_state(cfg, jax.random.PRNGKey(1), None,
                              learning_rate=lr)
        out, _ = train_step(st, cfg, None, tokens, mask, rewards, gids)
        return sum(float(jnp.abs(a - b).sum()) for a, b in zip(
            jax.tree_util.tree_leaves(st.params),
            jax.tree_util.tree_leaves(out.params)))

    d_small, d_big = delta(1e-5), delta(1e-2)
    # adamw step magnitude scales ~linearly with lr: a 1000x lr gap must
    # show up as a >=100x parameter-delta gap (it was ~1x when broken).
    assert d_big > 100 * d_small, (d_small, d_big)
    # and the state carries its optimizer through updates
    st = make_train_state(cfg, jax.random.PRNGKey(1), None,
                          learning_rate=1e-2)
    out, _ = train_step(st, cfg, None, tokens, mask, rewards, gids)
    assert out.opt is st.opt is not None


def test_entropy_bonus_engages():
    """Regression (r3): GRPOConfig.entropy_coef was declared but never
    used. With the bonus on, the loss shifts by -coef*entropy and the
    metric reports the sampled-surprisal estimate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.training import make_train_state, train_step
    from senweaver_ide_tpu.training.grpo import GRPOConfig

    cfg = get_config("tiny-test")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 512)
    mask = jnp.ones((4, 16), jnp.bool_)
    rewards = jnp.asarray([1.0, -1.0, 0.5, -0.5])
    gids = jnp.zeros((4,), jnp.int32)

    st = make_train_state(cfg, jax.random.PRNGKey(1), None)
    _, m0 = train_step(st, cfg, None, tokens, mask, rewards, gids,
                       grpo_config=GRPOConfig(entropy_coef=0.0))
    st = make_train_state(cfg, jax.random.PRNGKey(1), None)
    _, m1 = train_step(st, cfg, None, tokens, mask, rewards, gids,
                       grpo_config=GRPOConfig(entropy_coef=0.1))
    assert m1["entropy"] > 0                       # ~log(512) at init
    np.testing.assert_allclose(
        float(m1["loss"]), float(m0["loss"]) - 0.1 * float(m1["entropy"]),
        atol=1e-5)
    # accum path carries the metric too
    st = make_train_state(cfg, jax.random.PRNGKey(1), None)
    _, m2 = train_step(st, cfg, None, tokens, mask, rewards, gids,
                       grpo_config=GRPOConfig(entropy_coef=0.1),
                       accum_steps=2)
    assert "entropy" in m2 and np.isfinite(float(m2["entropy"]))


def test_grpo_round_anchored_reference(tmp_path, tiny_stack):
    """ref_params + kl_coef engage the k3-KL term inside the round: on
    the FIRST update the policy equals the anchor, so kl must be ~0 and
    the update must still be finite (the stabilizer for long contextual
    runs)."""
    from senweaver_ide_tpu.training.grpo import GRPOConfig
    config, state = tiny_stack
    tok = ByteTokenizer()

    def make_session():
        engine = RolloutEngine(state.params, config, num_slots=2,
                               max_len=4096, eos_id=tok.eos_id, seed=3)
        client = EnginePolicyClient(engine, tok, model_name="tiny-test",
                                    default_max_new_tokens=6,
                                    record_calls=True)
        return RolloutSession(client, str(tmp_path / "anch"),
                              include_tool_definitions=False)

    def reward(task_idx, g, session):
        return 1.0 if g % 2 == 0 else -1.0

    out = grpo_round(state, config, None, make_session, ["task"],
                     group_size=2, pad_id=tok.pad_id, max_len=2048,
                     reward_override=reward,
                     grpo_config=GRPOConfig(kl_coef=0.05),
                     ref_params=state.params)
    assert np.isfinite(out.metrics["loss"])
    # policy == anchor on the first update: k3 KL at the sampled tokens
    # is 0 up to numerical noise
    assert abs(out.metrics["kl"]) < 1e-3, out.metrics
