"""OnlineImprovementLoop on REAL weights (eval_online_real.py).

Round-3 review asked for an online-loop test with no
RuleSensitivePolicy anywhere: every episode here is sampled by a real
(random-init) transformer through the engine, judged from its own token
ids, trained on the reward head's finalReward, with the APO half wired
through the bank proposer. The full learning dynamics live in
ONLINE_r04.json; this pins the plumbing at test budget."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from eval_online_real import run_online_eval


def test_online_loop_real_weights_plumbing():
    # 4 rounds x (3 tasks x 2 group) = 24 traces: crosses the APO
    # auto-analyze gate (min 20 traces / 10 feedbacks) so the loop's
    # APO half actually executes inside the test.
    report = run_online_eval(rounds=4, ckpt=None, pretrain_rounds=2,
                             group_size=2, max_attempts=2)
    assert report["rounds"] == 4
    assert len(report["curve"]) == 4
    assert report["reward_source"].startswith("9-dim reward head")
    assert report["policy"].startswith("real transformer")
    for p in report["per_round"]:
        # every episode was judged (good_rate defined) and attempts
        # counted from the real client call log
        assert 0.0 <= p["good_rate"] <= 1.0
        assert p["mean_attempts"] >= 1.0
        assert isinstance(p["rules_active"], list)
    # the APO gates opened once >=20 feedback'd traces accumulated
    assert any(p["analyzed"] for p in report["per_round"])
    assert report["prior_frac_low_initial"] is not None
