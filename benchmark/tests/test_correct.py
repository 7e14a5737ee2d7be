"""The output check has teeth: the controls (the reference rounded through a
lower precision in the engine's place; the engine with its own quantized
KV) come out not ok, and a run whose timed path is broken underneath comes
out ``correct: false``. Both drive
``run.py`` itself at tiny-test widths on the CPU (``--rehearse`` skips only
the look for a chip)."""

import json
import sys

import pytest

from benchmark import run as bench_run

CHAT, GRPO = "qwen1.5b-chat-open", "qwen1.5b-grpo-rollout-sat"


def drive(monkeypatch, capsys, cell, *extra, trace="0"):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--rehearse", "--workload", cell,
        "--seed", str(2**31 + 5), "--seconds", "2", "--trace", trace, *extra])
    assert bench_run.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    compared = {ln.split()[1].rstrip(":"): ln for ln in lines
                if ln.startswith("compared ")}
    return json.loads(lines[-1]), compared


@pytest.mark.parametrize("cell", [CHAT, GRPO])
def test_sound_run_is_correct_and_the_control_is_not(monkeypatch, capsys,
                                                     cell):
    result, compared = drive(monkeypatch, capsys, cell, "--control", "bf16")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert compared["served_logp_gap_mean"].endswith(": ok")
    assert compared["served_logp_gap_max"].endswith(": ok")
    # tiny-test states float32 at highest: its control is bfloat16
    assert compared["control_bf16_logp_gap_mean"].endswith("NOT ok")


def test_each_run_reports_its_cells_metrics_and_no_cpu_number(monkeypatch,
                                                              capsys):
    result, _ = drive(monkeypatch, capsys, CHAT)
    assert result["metrics"] == {}
    assert result["rehearsal"] == ["itl_p99_ms", "setup_s", "ttft_p75_ms"]
    result, _ = drive(monkeypatch, capsys, CHAT, "--trace-seconds", "1",
                      trace="1")
    # the readers that need no device trace found something to read
    assert {"ttft_p90_ms.ttft", "ttft_steps_p90.ttft", "window_compiles",
            "setup_compile_s"} <= set(result["rehearsal"])


@pytest.mark.parametrize("kv", ["int8", "fp8"])
def test_the_engine_with_its_own_quantized_kv_is_not_correct(monkeypatch,
                                                             capsys, kv):
    result, compared = drive(monkeypatch, capsys, CHAT,
                             "--engine-kv-dtype", kv)
    assert result["correct"] is False
    assert compared["served_logp_gap_mean"].endswith("NOT ok")


def test_every_finished_request_is_compared(monkeypatch, capsys):
    result, compared = drive(monkeypatch, capsys, GRPO)
    # closed loop: attempted is what the window finished, and every served
    # token of those is compared (tiny outputs are 8-24 tokens a request)
    n = -float(compared["served_tokens_compared_min"].split()[2])
    assert result["correct"] is True
    assert 8 * result["attempted"] <= n <= 24 * result["attempted"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                             capsys):
    from senweaver_ide_tpu.rollout import RolloutEngine
    real = RolloutEngine.result

    def altered(self, rid):
        toks = real(self, rid)
        if len(toks) > 2:
            toks[1] = (toks[1] + 1) % self.config.vocab_size
        return toks

    monkeypatch.setattr(RolloutEngine, "result", altered)
    result, compared = drive(monkeypatch, capsys, CHAT)
    assert result["correct"] is False
    assert compared["served_logp_gap_max"].endswith("NOT ok")


def test_a_request_cut_short_is_not_correct(monkeypatch, capsys):
    from senweaver_ide_tpu.rollout import RolloutEngine
    real = RolloutEngine.submit

    def short(self, prompt, *, max_new_tokens=128, **kw):
        return real(self, prompt, max_new_tokens=max(1, max_new_tokens - 1),
                    **kw)

    monkeypatch.setattr(RolloutEngine, "submit", short)
    result, compared = drive(monkeypatch, capsys, CHAT)
    assert result["correct"] is False and result["failed"] > 0
    assert compared["finished_with_wrong_length"].endswith("NOT ok")
