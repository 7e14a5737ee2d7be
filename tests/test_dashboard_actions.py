"""Dashboard operator actions: click-path → control RPC → state change.

Round-3 review: the dashboard was a GET-only viewer while the
reference UI *drives* the system (suggestion apply/reject, job control —
apoService.ts:1375-1458 segment lifecycle, browser/react/src). These
tests run the full round trip over real transports: HTTP POST
/api/action → unix-socket JSON-RPC with the operator's token →
ControlServer handler → mutated service state visible in the next
GET /api/state. Auth is enforced by the CONTROL plane (the dashboard
holds no credentials), so a missing/bad token fails even though the
HTTP port is open.
"""

import json
import urllib.error
import urllib.request

import pytest

from senweaver_ide_tpu.apo.service import APOService, install_apo_channel
from senweaver_ide_tpu.apo.types import new_suggestion
from senweaver_ide_tpu.runtime.control import ControlServer
from senweaver_ide_tpu.services.config import (RuntimeConfig,
                                               install_config_channel)
from senweaver_ide_tpu.services.dashboard import DashboardService
from senweaver_ide_tpu.traces.collector import TraceCollector

TOKEN = "test-operator-token"


def _post(port, method, params=None, token=TOKEN):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/action",
        data=json.dumps({"method": method, "params": params}).encode(),
        headers={"Content-Type": "application/json",
                 **({"X-Auth-Token": token} if token else {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get_state(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/state", timeout=10) as r:
        return json.loads(r.read())


@pytest.fixture()
def stack(tmp_path):
    collector = TraceCollector()
    apo = APOService(collector)
    config = RuntimeConfig(settings_path=str(tmp_path / "settings.json"))
    server = ControlServer(str(tmp_path / "ctl.sock"), token=TOKEN)
    install_apo_channel(server, apo)
    install_config_channel(server, config)
    server.start()
    dash = DashboardService(collector=collector, apo=apo, control=server)
    port = dash.start()
    yield port, apo, config, server
    dash.stop()
    server.stop()


def test_auth_enforced_by_control_plane(stack):
    port, _apo, _config, server = stack
    status, body = _post(port, "submit", {"kind": "grpo"}, token=None)
    assert status == 401 and not body["ok"]
    status, body = _post(port, "submit", {"kind": "grpo"}, token="wrong")
    assert status == 401 and not body["ok"]
    assert server.list_jobs() == []          # nothing got through


def test_job_submit_then_stop_roundtrip(stack):
    port, _apo, _config, _server = stack
    status, body = _post(port, "submit", {"kind": "grpo", "rounds": 2})
    assert status == 200 and body["ok"]
    job_id = body["result"]["job_id"]
    jobs = {j["job_id"]: j for j in _get_state(port)["jobs"]}
    assert jobs[job_id]["status"] == "queued"

    status, body = _post(port, "stop", {"job_id": job_id})
    assert status == 200 and body["ok"]
    jobs = {j["job_id"]: j for j in _get_state(port)["jobs"]}
    assert jobs[job_id]["status"] == "stopped"


def test_apo_suggestion_apply_reject_roundtrip(stack):
    port, apo, _config, _server = stack
    apo.segments.add_suggestions([
        new_suggestion(target_category="tool_usage", type="add",
                       priority="high", description="verify first",
                       reasoning="r", estimated_impact="high",
                       suggested_content="Verify inputs before acting."),
        new_suggestion(target_category="general", type="add",
                       priority="low", description="noise",
                       reasoning="r", estimated_impact="low",
                       suggested_content="Do something unhelpful."),
    ])
    state = _get_state(port)
    rows = {r["description"]: r for r in state["apo"]["suggestions"]}
    assert rows["verify first"]["status"] == "pending"

    status, body = _post(port, "apo.apply",
                         {"id": rows["verify first"]["id"]})
    assert status == 200 and body["ok"]
    assert "Verify inputs before acting." in body["result"]["rules"]
    status, body = _post(port, "apo.reject", {"id": rows["noise"]["id"]})
    assert status == 200 and body["ok"]

    state = _get_state(port)
    rows = {r["description"]: r for r in state["apo"]["suggestions"]}
    assert rows["verify first"]["status"] == "applied"
    assert rows["noise"]["status"] == "rejected"
    assert "Verify inputs before acting." in \
        state["apo"]["optimized_rules"]
    # revert undoes the applied segment
    status, body = _post(port, "apo.revert",
                         {"id": rows["verify first"]["id"]})
    assert status == 200 and body["ok"]
    assert "Verify inputs before acting." not in body["result"]["rules"]


def test_apo_analyze_and_unknown_id_errors(stack):
    port, _apo, _config, _server = stack
    status, body = _post(port, "apo.analyze")
    assert status == 200 and body["ok"]
    assert "good_rate" in body["result"]
    status, body = _post(port, "apo.apply", {"id": "nope"})
    assert status == 400 and not body["ok"]


def test_config_push_roundtrip(stack):
    port, _apo, config, _server = stack
    status, body = _post(port, "config.push",
                         {"allowed_models": ["tiny-test"]})
    assert status == 200 and body["ok"]
    assert config.is_model_allowed("tiny-test")
    assert not config.is_model_allowed("other-model")


def test_no_control_socket_is_503(tmp_path):
    dash = DashboardService(collector=TraceCollector())
    port = dash.start()
    try:
        status, body = _post(port, "submit", {})
        assert status == 503 and not body["ok"]
    finally:
        dash.stop()
