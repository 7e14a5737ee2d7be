"""What PR 39 brings for ``phi-4-mini-flash-reasoning``: the configuration
file against the catalog's published keys (nothing cut) and a recount of its
bytes, the architecture map, the step's cost on hand-counted sizes, the
reference against the program through the engine at a small size and its
control, the new reader on hand-made records, and the manifest's new entries
— each by NAME, never by count, position or set of all cells."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import phi4flash as arch
from benchmark.costs import fused_step_sambay as cost
from benchmark.manifest import HERE, ROOT, Manifest, load_json
from benchmark.readers import (program_span_attr, program_span_ratio,
                               sambay_step_roofline)

CELL = "phi4-mini-flash-grpo-rollout-ctx4k"
CONFIG = "phi-4-mini-flash-reasoning"
CFG = load_json(HERE, "configs", CONFIG + ".json")
# the catalog row's ``config``: the model's own public config.json
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
ASSUMED = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
           "mamba_dt_rank": 160}
# the same pattern at test size
TINY = dict(
    PUBLISHED, name="tiny-phi4flash", hidden_size=96, intermediate_size=128,
    num_attention_heads=24, num_key_value_heads=12, num_hidden_layers=8,
    max_position_embeddings=128, sliding_window=8, vocab_size=512,
    mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=6,
    torch_dtype="float32", matmul_precision="highest")


def test_configuration_file_is_the_published_one_with_nothing_cut():
    assert {k: CFG[k] for k in PUBLISHED} == PUBLISHED
    assert CFG["reduced"] == [] and CFG["published"][
        "num_hidden_layers"] == 32
    assert {k: CFG[k] for k in ASSUMED} == ASSUMED
    assert set(ASSUMED) <= set(CFG["assumed"])      # each with its reason
    assert CFG["reference"] == "phi4flash"
    assert {"assumed", "deployment", "kept", "bytes", "published"} <= set(CFG)
    entry = Manifest(CELL).config_entry
    assert entry["name"] == CONFIG and entry["reduced"] == []
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_files_bytes_are_a_recount_from_its_keys():
    d, f, v = CFG["hidden_size"], CFG["intermediate_size"], CFG["vocab_size"]
    hq, hkv = CFG["num_attention_heads"], CFG["num_key_value_heads"]
    dh = d // hq
    inner = CFG["mamba_expand"] * d
    n, k, r = (CFG["mamba_d_state"], CFG["mamba_d_conv"],
               CFG["mamba_dt_rank"])
    b = CFG["bytes"]
    assert b["embedding_params"] == v * d == 512_163_840
    assert b["mlp_params_per_layer"] == 3 * d * f == 78_643_200
    assert b["mixer_params_per_layer"] == (
        d * 2 * inner + inner * (r + 2 * n) + r * inner + inner * d
    ) == 41_123_840
    assert b["attention_params_per_layer"] == (
        d * hq * dh + 2 * d * hkv * dh + hq * dh * d) == 19_660_800
    assert b["cross_params_per_layer"] == 2 * d * hq * dh == 13_107_200
    assert b["gmu_params_per_layer"] == 2 * d * inner == 26_214_400
    assert b["mixer_small_params_per_layer"] == ((k + 1) * inner
                                                 + inner * n + 2 * inner)
    assert b["attention_small_params_per_layer"] == 4 * dh + 2 * dh
    assert b["params"] == (
        b["embedding_params"] + 32 * b["mlp_params_per_layer"]
        + 9 * (b["mixer_params_per_layer"]
               + b["mixer_small_params_per_layer"])
        + 9 * b["attention_params_per_layer"]
        + 7 * b["cross_params_per_layer"] + 7 * b["gmu_params_per_layer"]
        + 16 * b["attention_small_params_per_layer"]
        + 32 * b["norm_params_per_layer"] + b["final_norm_params"]
    ) == 3_852_457_984
    assert b["float32_params"] == 9 * (inner * n + 2 * inner) + 16 * 4 * dh
    assert b["weights_bf16_bytes"] == (2 * b["params"]
                                       + 2 * b["float32_params"])
    mix = load_json(HERE, "traffic", "grpo-rollout-ctx4k.json")["engine"]
    slots, max_len = mix["num_slots"], mix["max_len"]
    kv = b["kv_bytes_per_token_per_layer"]
    assert kv == 2 * hkv * dh * 2 == 5120
    assert b["kv_pool_bytes"] == (slots + 4) * max_len * kv
    assert b["state_rows"] == slots + max(2, slots // 6) == 56
    bs = b["block_size"]
    assert bs * kv // 2 >= 64 << 10 > (bs // 2) * kv // 2     # 64 KiB a copy
    assert b["window_capacity"] == -(-(CFG["sliding_window"]
                                       + 4 * slots) // bs) * bs == 704
    assert b["window_pool_bytes"] == (8 * b["state_rows"]
                                      * b["window_capacity"] * kv)
    assert b["state_pool_bytes"] == 9 * b["state_rows"] * inner * n * 4
    assert b["conv_pool_bytes"] == 9 * b["state_rows"] * (k - 1) * inner * 2
    share = (b["weights_bf16_bytes"] + b["kv_pool_bytes"]
             + b["window_pool_bytes"] + b["state_pool_bytes"]
             + b["conv_pool_bytes"]) / 17.18e9
    assert 0.60 < share < 0.63
    # and the cost file counts the same matrices
    s = cost.sizes(CFG)
    assert s["layer_params_total"] + s["embed_params"] == (
        b["params"] - 9 * b["mixer_small_params_per_layer"]
        - 16 * b["attention_small_params_per_layer"]
        - 32 * b["norm_params_per_layer"] - b["final_norm_params"])
    assert s["kinds"] == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                          "cross": 7}


UNMAPPED = [
    ("hidden_act", "gelu"), ("mb_per_layer", 4), ("num_hidden_layers", 30),
    ("tie_word_embeddings", False), ("mlp_bias", True),
    ("lm_head_bias", True), ("embd_pdrop", 0.1), ("resid_pdrop", 0.1),
    ("num_key_value_heads", 15), ("hidden_size", 2570)]


@pytest.mark.parametrize("key,value", UNMAPPED,
                         ids=[k for k, _ in UNMAPPED])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.num_layers, c.num_heads, c.num_kv_heads, c.head_dim) == (
        32, 40, 20, 64)
    assert (c.mamba_d_ssm, c.mamba_d_state, c.mamba_d_conv,
            c.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (c.cache_kv_heads, c.cache_head_dim, c.layer_window) == (
        10, 128, 512)
    assert c.pattern and c.ssm and c.diff_attn
    assert c.norm == "layer" and c.tie_word_embeddings
    assert c.sliding_window is None        # no one window for every layer
    assert [c.kind_layers(k) for k in ("mamba", "window", "full", "gmu",
                                       "cross")] == [9, 8, 1, 7, 7]
    assert c.attn_layers == 1 and c.max_seq_len == 262144


def test_step_cost_by_hand():
    """One small shape by hand: 8 layers of hidden 8, MLP 16, 4 query / 2
    kv heads x 2, a mixer of 16 x 3 with a rank of 2 and a 4-tap conv, a
    window of 5, 100 ids; then ISSUE 39's narrow step at the published
    widths."""
    small = {"hidden_size": 8, "intermediate_size": 16,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_hidden_layers": 8, "mamba_expand": 2, "mamba_d_state": 3,
             "mamba_d_conv": 4, "mamba_dt_rank": 2, "sliding_window": 5,
             "vocab_size": 100}
    s = cost.sizes(small)
    assert s["kinds"] == {"mamba": 3, "window": 2, "full": 1, "gmu": 1,
                          "cross": 1}
    assert s["attn_params"] == 8 * 8 + 2 * 8 * 4 + 8 * 8 == 192
    assert s["cross_params"] == 2 * 8 * 8 == 128
    assert s["mixer_params"] == 8 * 32 + 16 * (2 + 6) + 2 * 16 + 16 * 8 == 544
    assert s["gmu_params"] == 2 * 8 * 16 == 256
    total = 3 * 544 + 3 * 192 + 128 + 256 + 8 * 3 * 8 * 16
    assert s["layer_params_total"] == total == 5664
    assert (s["state_values"], s["window_values"], s["full_passes"]) == (
        16 * 3, 3 * 16, 2)
    # 7 tokens, 3 sampled, decoding rows hold 50 tokens of KV of which 20
    # are read once for several rows, 12 window columns, 4 rows moved
    ops, byts = cost.ops_and_bytes(small, 7, 3, 50, 20, 12, 4)
    assert ops == (2 * 5664 * 7 + 2 * 800 * 3
                   + 4 * 4 * 2 * (2 * 50 + 2 * 12) + 3 * 6 * 48 * 7)
    kv = 2 * (2 * 2 * 2)
    assert byts == (2 * (5664 + 800) + kv * (2 * (50 - 20) + 7)
                    + kv * 2 * (12 + 7) + 2 * 3 * 4 * (4 * 48 + 2 * 48)
                    + 3 * 2 * 8 * 7)
    # rows the step did not advance cost nothing
    assert cost.ops_and_bytes(small, 7, 3, 50, 20, 12, 0)[1] == (
        byts - 2 * 3 * 4 * (4 * 48 + 2 * 48))
    # 48 decode rows at 2500 tokens of context of which 2000 are a group's
    # prompt read once for its 8 rows, every row's state moved: 7.70 GB of
    # weights (the tied embedding once) + 8 passes over 36,000 columns of
    # 5120 B (1.47 GB) + 8 windows (1.01 GB) + 0.29 GB of state: 12.8 ms
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    shared = 6 * 7 * 2000
    args = (48, 48, 48 * 2500, shared, 48 * 512, 48)
    ops, byts = cost.ops_and_bytes(CFG, *args)
    t = cost.least_seconds(CFG, peaks, *args)
    assert t == byts / 819e9 and 12.5e-3 < t < 13.1e-3
    assert 0.13 < 8 * 5120 * (48 * 2500 - shared) / byts < 0.15
    # unshared, the eight passes are 4.9 GB of a 13.9 GB step
    assert 16.8e-3 < cost.least_seconds(
        CFG, peaks, 48, 48, 48 * 2500, 0, 48 * 512, 48) < 17.2e-3
    # a wide step is still bound by its bytes
    ops, byts = cost.ops_and_bytes(CFG, 192, 48, 40 * 2500, 0, 40 * 512, 41)
    assert ops / 197e12 < byts / 819e9


# ---- the reference against the program, through the engine ---------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmark.weights import make_weights
    config = arch.model_config(TINY)
    return make_weights(config, 3900000124), config


def test_seeded_weights_are_what_the_file_assumes(tiny_model):
    """``weights.py``, unedited, fills every new leaf with finite,
    non-degenerate values: gains 1, LayerNorm biases and the mixer's small
    leaves normal / sqrt(layers), A_log near 0, lambda vectors small."""
    lp = tiny_model[0]["layers"]
    for seg in lp.values():
        for kind in seg.values():
            for name, leaf in kind.items():
                a = np.asarray(leaf, np.float32)
                assert np.isfinite(a).all(), name
                if name.endswith("norm"):
                    assert float(abs(a - 1.0).max()) == 0.0
                else:
                    assert a.std() > 0.01, name
    mix = lp["seg0"]["mamba"]
    assert str(mix["ssm_A_log"].dtype) == "float32"
    assert float(np.abs(np.asarray(mix["ssm_A_log"])).max()) < 0.5
    lam = np.asarray(lp["seg1"]["full"]["attn_lambda"])[0]
    assert abs(float(np.exp(lam[:, 0] @ lam[:, 1])
                     - np.exp(lam[:, 2] @ lam[:, 3]))) < 2.0
    assert tiny_model[0]["final_norm_bias"].shape == (1, 96)


def test_served_logps_agree_with_the_engine_and_the_control_does_not(
        tiny_model):
    """What ``correct.py`` compares on the chip, at test size: a group of
    three (one prefill; refcounts, state rows and rings forked) and a lone
    request, sampled at temperature 1, contexts of 4 windows. float32 at
    ``highest`` on both sides: 3e-5, summation order. The fp8 control
    rounds every matrix product's inputs."""
    from benchmark.reference import phi4flash as ref
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    params, config = tiny_model
    eng = RolloutEngine(
        params, config, num_slots=4, max_len=64,
        sample=SampleParams(temperature=1.0, top_k=0, top_p=1.0),
        engine_config=EngineConfig(block_size=4, step_tokens=8))
    group = list(range(1, 24))
    rids = eng.submit_group(group, 3, max_new_tokens=9)
    lone = eng.submit([7, 7, 7], max_new_tokens=9)
    eng.run()
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    assert eng.stats()["group_forks"] == 2
    gaps = []
    for p, rid in [(group, r) for r in rids] + [([7, 7, 7], lone)]:
        seq = np.asarray([p + eng.result(rid)], np.int32)
        want = np.asarray(ref.served_logps(params, TINY, seq, [len(p) - 1],
                                           9))[0]
        low = np.asarray(ref.served_logps(params, TINY, seq, [len(p) - 1],
                                          9, quant="fp8"))[0]
        assert np.abs(np.asarray(eng.result_logps(rid)) - want).max() < 3e-5
        gaps.append(np.abs(low - want).mean())
    assert min(gaps) > 30 * 3e-5


def test_the_references_blocked_head_is_the_whole_head(tiny_model,
                                                       monkeypatch):
    """The tied head and its log-sum-exp in vocabulary blocks of 128 rows
    (four blocks at this size) against the whole head's log-softmax."""
    from benchmark.reference import phi4flash as ref
    params, _ = tiny_model
    toks = np.arange(40, dtype=np.int32)[None] * 11 % 512
    logits = np.asarray(ref.logits(params, TINY, toks))[0]
    whole = logits - np.log(np.exp(
        logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)
    ) - logits.max(-1, keepdims=True)
    want = np.asarray([whole[9 + j, toks[0, 10 + j]] for j in range(20)])
    monkeypatch.setattr(ref, "V_BLOCK", 128)
    got = np.asarray(ref.served_logps(params, TINY, toks, [9], 20))[0]
    assert np.abs(got - want).max() < 1e-5
    assert CFG["vocab_size"] % ref.V_BLOCK == 0


# ---- the new reader and the new metrics' files ----------------------------

def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


def test_sambay_step_roofline_reads_the_programs_steps(monkeypatch):
    spec = load_json(HERE, "layer_metrics",
                     "sambay_step_roofline.rollout.json")
    assert spec["reader"] == "sambay_step_roofline"
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    steps = [span("engine.step", used=48, decode_rows=48, ssm_rows=48,
                  kv_columns_window=8 * 48 * 512, kv_blocks_saved=2600,
                  block_size=32),
             span("engine.step", used=190, decode_rows=46, ssm_rows=47,
                  kv_columns_window=8 * 30000),      # collected later
             span("engine.step"),                    # a step with no plan
             span("engine.emit", used=1, decode_rows=1, ssm_rows=1,
                  kv_columns_window=1)]
    host = [{"decode": 48, "sampled": 48, "contexts": 120000},
            {"decode": 46, "sampled": 47, "contexts": 110000}]
    r = types.SimpleNamespace(
        config_file=CFG, peaks=peaks, traced_steps=host,
        trace=types.SimpleNamespace(modules={
            "jit__paged_fused_step(123)": [(0, 20e6), (1, 30e6)],
            "jit_copy_state_rows": [(2, 1e6)]}))
    monkeypatch.setattr(sambay_step_roofline, "recorded", lambda r: steps)
    least = (cost.least_seconds(CFG, peaks, 48, 48, 120000, 2600 * 32,
                                48 * 512, 48)
             + cost.least_seconds(CFG, peaks, 190, 47, 110000, 0, 46 * 512,
                                  47))
    got = sambay_step_roofline.read(r, spec["args"])
    assert got == pytest.approx(100.0 * least / 50e-3) and 40 < got < 70
    # the parent's spans carry no such attr; another configuration; no
    # trace: left out, nothing raises
    monkeypatch.setattr(sambay_step_roofline, "recorded",
                        lambda r: [span("engine.step", used=48)])
    assert sambay_step_roofline.read(r, spec["args"]) is None
    monkeypatch.setattr(sambay_step_roofline, "recorded", lambda r: steps)
    r.config_file = load_json(HERE, "configs", "qwen2.5-coder-1.5b.json")
    assert sambay_step_roofline.read(r, spec["args"]) is None
    r.config_file, r.trace = CFG, None
    assert sambay_step_roofline.read(r, spec["args"]) is None


def test_the_column_shares_and_the_copies_read_the_steps_attrs(monkeypatch):
    steps = [span("engine.step", kv_columns=1000, kv_columns_window=200,
                  kv_columns_full=100, kv_columns_cross=700,
                  window_row_copies=0),
             span("engine.step", kv_columns=3000, kv_columns_window=300,
                  kv_columns_full=300, kv_columns_cross=2400,
                  window_row_copies=16),
             span("engine.step"),
             span("engine.plan", kv_columns=9, kv_columns_cross=9,
                  window_row_copies=99)]
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: steps)
    monkeypatch.setattr(program_span_attr, "recorded", lambda r: steps)
    want = {"cross_kv_column_share.rollout": 100.0 * 3100 / 4000,
            "window_kv_column_share.rollout": 100.0 * 500 / 4000}
    for name, value in want.items():
        spec = load_json(HERE, "layer_metrics", name + ".json")
        assert spec["reader"] == "program_span_ratio"
        assert program_span_ratio.read(None, spec["args"]) == value
    spec = load_json(HERE, "layer_metrics",
                     "window_row_copies_max.rollout.json")
    assert program_span_attr.read(None, spec["args"]) == 16.0
    # a program from before the attrs: nothing to read, nothing raised
    monkeypatch.setattr(program_span_ratio, "recorded",
                        lambda r: [span("engine.step", used=48)])
    monkeypatch.setattr(program_span_attr, "recorded",
                        lambda r: [span("engine.step", used=48)])
    for name in list(want) + ["window_row_copies_max.rollout"]:
        spec = load_json(HERE, "layer_metrics", name + ".json")
        reader = (program_span_attr if "copies" in name
                  else program_span_ratio)
        assert reader.read(None, spec["args"]) is None


# ---- the manifest: this PR's entries, by name ------------------------------

NEW_METRICS = {"sambay_step_roofline.rollout": "device_trace",
               "cross_kv_column_share.rollout": "program_counter",
               "window_kv_column_share.rollout": "program_counter",
               "window_row_copies_max.rollout": "program_counter"}
JOINED = ["rollout_tok_s", "fused_step_ms.rollout",
          "device_idle_share.rollout", "hbm_peak_share.rollout",
          "idle_inside_programs_share.rollout",
          "engine_unqueued_share.rollout", "run_ahead_share.rollout",
          "attn_shared_block_share.rollout", "ssm_state_copies_max.rollout"]


def test_the_new_cell_reports_what_the_issue_lists():
    doc = load_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "grpo-rollout-ctx4k", 1)
    man = Manifest(CELL)
    assert [m["name"] for m in man.end_to_end()] == ["rollout_tok_s",
                                                     "setup_s"]
    reported = {m["name"] for m in man.per_layer()}
    assert reported == set(NEW_METRICS) | set(JOINED[1:]) | {
        "setup_compile_s", "window_compiles"}
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name, source in NEW_METRICS.items():
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert m["layer"] == "fused step" and m["source"] == source
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           name + ".json"))
    assert metrics["sambay_step_roofline.rollout"]["unit"] == "%"
    for name in JOINED:
        assert metrics[name]["workloads"][-1] == CELL
    # null or nonsense under run-ahead (PERF.md section 7 (4c)): not joined
    for name, m in metrics.items():
        if name.startswith(("idle_gap_", "engine_host_ms_per_step")):
            assert CELL not in m["workloads"]
    limits = man.limits
    assert (0 < limits["served_logp_gap_mean"]
            < limits["served_logp_gap_max"])
    assert os.path.exists(os.path.join(HERE, "traffic",
                                       cell["traffic"] + ".json"))


def test_rehearsal_of_the_new_cell_leaves_the_model_metrics_out():
    """The cell's control flow on the CPU at tiny-test sizes (a plain dense
    model): the readers find no pattern, and say nothing."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--trace-seconds", "1"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert {"device_idle_share.rollout", "window_compiles"} <= set(
        line["rehearsal"])
    assert not set(NEW_METRICS) & set(line["rehearsal"])
