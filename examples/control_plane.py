"""Quickstart: drive real training through the C++ control plane.

Starts a ControlServer + JobRunner around a hermetic session factory,
then uses the senweaver-ctl binary (built on demand from
native/senweaver_ctl.cpp) to submit a GRPO job, watch it, and fetch its
metrics — the operator workflow for a long-running trainer process.

    python examples/control_plane.py
"""
import json, subprocess, sys, tempfile
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import jax
jax.config.update("jax_platforms", "cpu")
from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
enable_compile_cache()
from senweaver_ide_tpu.apo.eval import GOOD_RULESET, RuleSensitivePolicy
from senweaver_ide_tpu.models import get_config
from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
from senweaver_ide_tpu.rollout import RolloutSession
from senweaver_ide_tpu.runtime import ControlServer, JobRunner
from senweaver_ide_tpu.runtime.native import ctl_binary_path
from senweaver_ide_tpu.training import make_train_state

config = get_config("tiny-test")
state = make_train_state(config, jax.random.PRNGKey(0), None, learning_rate=1e-3)
tok = ByteTokenizer()
tmp = tempfile.mkdtemp()
n = [0]

class RecordingPolicy:
    """Scripted policy + the (prompt_ids, out_ids) call log that
    collect_group_trajectories slices into GRPO trajectories — without
    it a round collects zero training data."""

    def __init__(self):
        self.inner = RuleSensitivePolicy(); self.call_log = []
    def chat(self, messages, **kw):
        r = self.inner.chat(messages, **kw)
        self.call_log.append(( tok.encode("\n".join(m.content for m in messages))[-128:],
                               tok.encode(r.text)[:64]))
        return r

def make_session(rules=None):
    n[0] += 1
    s = RolloutSession(RecordingPolicy(), f"{tmp}/ws{n[0]}",
                       apo_rules=list(rules or []),
                       include_tool_definitions=False)
    s.workspace.write_file("app.py", "def run():\n    return 1\n")
    return s

server = ControlServer(f"{tmp}/ctl.sock")
runner = JobRunner(server, make_session=make_session, train_state=state,
                   model_config=config, max_len=512,
                   reward_override=lambda ti, g, s: 1.0 if g % 2 == 0 else -1.0)
server.start(); runner.start()
CTL = ctl_binary_path()
if CTL is None:
    sys.exit("senweaver-ctl binary unavailable (native build failed — "
             "install a C++ toolchain and rebuild native/senweaver_ctl.cpp)")

def ctl(*args):
    p = subprocess.run([CTL, "--socket", server.socket_path, "--interval", "1",
                        *args], capture_output=True, text=True, timeout=300)
    return json.loads([l for l in p.stdout.strip().split("\n") if l][-1])

job = ctl("submit", json.dumps({"type": "grpo", "tasks": ["fix the crash"],
                                "rounds": 2, "group_size": 2,
                                "ppo_epochs": 2}))["result"]["job_id"]
ctl("watch")
res = ctl("call", "job_result", json.dumps({"job_id": job}))["result"]
print("job", job, "->", res["status"], "| step", res["result"]["step"],
      "| rounds", res["result"]["rounds_done"])
ev = ctl("submit", json.dumps({"type": "eval_rules",
                               "rules": list(GOOD_RULESET)}))["result"]["job_id"]
ctl("watch")
score = ctl("call", "job_result", json.dumps({"job_id": ev}))["result"]["result"]
print("eval_rules finalReward:", round(score["final_reward"], 3))
runner.stop(); server.stop()
print("JOBS SESSION OK")
