"""What PR 43 brings for ``solar-open2-250b``: the configuration file against
the catalog's published keys (depth, experts held and vocabulary cut, no
width) and a recount of its bytes from its keys, the architecture map, the
step's cost on hand-counted sizes, the seeded weights' decays, the
reference against the program through the engine at a small size and its
control, the new reader on hand-made records, and the manifest's new entries
— each by NAME, never by count, position or set of all cells or metrics."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import solar_open2 as arch
from benchmark.costs import fused_step_kda_moe as cost
from benchmark.manifest import HERE, ROOT, Manifest, load_json
from benchmark.readers import (kda_step_roofline, program_span_attr,
                               program_span_ratio)

CELL = "solar-open2-grpo-rollout-ctx4k"
CONFIG = "solar-open2-250b"
CFG = load_json(HERE, "configs", CONFIG + ".json")
# the catalog row's ``config``: the model's own public config.json
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": 4, "n_routed_experts": 40,
           "vocab_size": 24576}
# the same layers at test size: one period, experts [2, 6) of 8
TINY = dict(
    PUBLISHED, name="tiny-solar-open2", hidden_size=64,
    num_attention_heads=4, head_dim=16, num_key_value_heads=2,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 8,
                        "num_heads": 4, "num_kv_heads": None},
    num_hidden_layers=4, vocab_size=512, intermediate_size=160,
    moe_intermediate_size=32, max_position_embeddings=128,
    n_routed_experts=4, num_experts_per_tok=3, torch_dtype="float32",
    matmul_precision="highest", published={"n_routed_experts": 8},
    held_experts={"first": 2, "count": 4, "of": 8})


def test_configuration_file_is_the_published_one_cut_in_depth_and_share():
    same = set(PUBLISHED) - set(REDUCED)
    assert {k: CFG[k] for k in same} == {k: PUBLISHED[k] for k in same}
    assert {k: CFG[k] for k in REDUCED} == REDUCED
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert {k: CFG["published"][k] for k in REDUCED} == {
        k: PUBLISHED[k] for k in REDUCED}
    assert CFG["held_experts"] == {"first": 0, "count": 40, "of": 320}
    # the guide's floors: a whole period and four layers, 8 experts, 1/8
    assert CFG["num_hidden_layers"] % (CFG["gqa_interval"] + 1) == 0
    assert CFG["num_hidden_layers"] >= 4 and CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for item in ("gqa_gate", "kda_rank", "kda_conv_bias", "kda_gate_bias",
                 "kda_out_norm", "kda_q_scale", "kda_l2_eps", "kda_float32",
                 "scoring_func", "correction_bias", "torch_dtype",
                 "hidden_act", "weights"):
        assert item in CFG["assumed"], item
    assert CFG["reference"] == "solar_open2"
    assert "8 chips share each layer" in CFG["deployment"]
    assert "96 chips" in CFG["deployment"]
    entry = Manifest(CELL).config_entry
    assert entry["name"] == CONFIG and entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_files_bytes_are_a_recount_from_its_keys():
    b = CFG["bytes"]
    d, hq, hkv, dh = 4096, 64, 8, 128
    lin = CFG["linear_attn_config"]
    w = lin["num_heads"] * lin["head_dim"]
    gqa = 3 * d * hq * dh + 2 * d * hkv * dh
    kda = (4 * d * w + 2 * (d * 128 + 128 * w) + d * 64 + 4 * 3 * w
           + 2 * w + 64 + 128)        # ..., dt_bias, g_bias, A_log, o_norm
    expert = 3 * d * CFG["moe_intermediate_size"]
    every = expert + d * 320
    norms = 2 * d + 320
    assert (gqa, kda, every, expert) == (
        b["gqa_mixer_params"], b["kda_mixer_params"],
        b["shared_expert_and_router_params"], b["expert_params"])
    assert 40 * expert == b["held_expert_params_per_layer"]
    assert gqa + every + norms + 40 * expert == b["gqa_layer_params"]
    assert kda + every + norms + 40 * expert == b["kda_layer_params"]
    assert b["gqa_layer_params"] + 3 * b["kda_layer_params"] == b[
        "period_params"]
    assert 2 * CFG["vocab_size"] * d == b["embedding_and_head_params"]
    assert (b["period_params"] + b["embedding_and_head_params"] + d
            == b["params"])
    # float32: A_log and dt_bias of three layers, four correction biases
    f32 = 3 * (64 + w) + 4 * 320
    assert 2 * b["params"] + 2 * f32 == b["weights_bytes"]
    mix = load_json(HERE, "traffic", "grpo-rollout-ctx4k.json")["engine"]
    rows, tokens = mix["num_slots"], mix["max_len"]
    assert b["kv_bytes_per_token"] == 2 * hkv * dh * 2
    assert b["kv_cache_bytes"] == (rows + 4) * tokens * b[
        "kv_bytes_per_token"]
    assert b["state_bytes_per_row_per_layer"] == 64 * 128 * 128 * 4
    assert b["state_bytes"] == (rows + 8) * 3 * b[
        "state_bytes_per_row_per_layer"]
    assert b["conv_bytes_per_row_per_layer"] == 3 * 3 * w * 2
    assert b["conv_bytes"] == (rows + 8) * 3 * b[
        "conv_bytes_per_row_per_layer"]
    share = (b["weights_bytes"] + b["kv_cache_bytes"] + b["state_bytes"]
             + b["conv_bytes"]) / 17.18e9
    assert 0.47 < share < 0.49
    # two periods would not fit beside any cache
    assert 2 * 2 * b["period_params"] > 0.72 * 17.18e9


UNMAPPED = [("use_rope", True), ("first_k_dense_replace", 1),
            ("kda_use_full_proj", True), ("tie_word_embeddings", True),
            ("norm_topk_prob", False), ("num_hidden_layers", 6),
            ("gqa_layers", [1, 5]), ("partial_rotary_factor", 0.5),
            ("hidden_act", "gelu"), ("n_group", 8), ("sliding_window", 4096)]


@pytest.mark.parametrize("key,value", UNMAPPED,
                         ids=[k for k, _ in UNMAPPED])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_raises_on_a_share_that_is_not_the_files():
    with pytest.raises(SystemExit, match="held_experts"):
        arch.model_config(dict(CFG, held_experts={"first": 0, "count": 32,
                                                  "of": 320}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.head_dim, c.vocab_size) == (4096, 4, 64, 8, 128, 24576)
    assert c.layer_types == ((("full", "kda", "kda", "kda"), 1),)
    assert (c.kda_num_heads, c.kda_head_dim, c.kda_conv, c.kda_rank,
            c.kda_neg_eigval, c.attn_out_gate) == (64, 128, 4, 128, True,
                                                   True)
    assert (c.num_experts, c.moe_routed_experts, c.moe_first_expert,
            c.num_experts_per_tok, c.expert_size, c.num_shared_experts,
            c.router_type, c.routed_scaling_factor) == (
        40, 320, 0, 8, 1280, 1, "sigmoid_bias", 1.0)
    assert not c.tie_word_embeddings and not c.diff_attn
    assert c.expert_share and c.pattern and c.ssm and not c.mla
    assert str(c.dtype) == "bfloat16" or c.dtype.__name__ == "bfloat16"


def test_step_cost_by_hand():
    """One small shape by hand: 4 layers of hidden 8, 4 query / 2 kv heads
    x 2, a mixer of 2 heads x 4 with a 4-tap conv, experts of width 3 (4
    held of 16), one shared, 100 ids; then ISSUE 43's narrow step at the
    published widths."""
    small = {"hidden_size": 8, "num_hidden_layers": 4,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 2, "gqa_interval": 3, "use_gqa_gate": True,
             "linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                    "short_conv_kernel_size": 4},
             "moe_intermediate_size": 3, "n_shared_experts": 1,
             "n_routed_experts": 4, "held_experts": {"of": 16},
             "vocab_size": 100}
    s = cost.sizes(small)
    assert (s["gqa_layers"], s["kda_layers"]) == (1, 3)
    assert s["gqa_params"] == 3 * 8 * 8 + 2 * 8 * 4 == 256
    assert s["kda_params"] == (4 * 8 * 8 + 2 * (8 * 4 + 4 * 8) + 8 * 2
                               + 4 * 3 * 8) == 496
    assert s["expert_params"] == 72
    assert s["shared_and_router_params"] == 72 + 8 * 16 == 200
    assert s["always_params"] == 256 + 3 * 496 + 4 * 200 == 2544
    assert (s["state_values"], s["window_values"]) == (2 * 4 * 4, 3 * 3 * 8)
    # 7 tokens, 3 sampled, decoding rows hold 50 tokens of KV of which 20
    # are read once for several rows, 4 rows moved, 5 chunk entries, 9 held
    # banks touched, 11 pairs
    args = (7, 3, 50, 20, 4, 5, 9, 11)
    ops, byts = cost.ops_and_bytes(small, *args)
    assert ops == (2 * 2544 * 7 + 2 * 72 * 11 + 2 * 800 * 3
                   + 1 * 4 * 4 * 2 * 50
                   + 3 * (6 * 32 * 7 + 2 * 2 * 8 * 0.5 * 5))
    assert byts == (2 * (2544 + 800 + 9 * 72) + 2 * 8 * (50 - 20 + 7)
                    + 2 * 3 * 4 * (4 * 32 + 2 * 72) + 3 * 2 * 8 * 7)
    # rows the step did not advance, banks it did not touch: nothing
    assert cost.ops_and_bytes(small, 7, 3, 50, 20, 0, 5, 0, 11)[1] == (
        byts - 2 * 3 * 4 * (4 * 32 + 2 * 72) - 2 * 9 * 72)
    # 48 decode rows at 2500 tokens of context of which 2000 are a group's
    # prompt read once for its 8 rows, every row's state moved, 112 of the
    # 160 held banks touched by 230 pairs: weights outside the banks 1.18
    # GB, banks 3.52, state 1.21 + windows 0.04, KV 0.15, head 0.20
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    shared = 6 * 7 * 2000
    narrow = (48, 48, 48 * 2500, shared, 48, 0, 112, 230)
    ops, byts = cost.ops_and_bytes(CFG, *narrow)
    t = cost.least_seconds(CFG, peaks, *narrow)
    assert t == byts / 819e9 and 7.5e-3 < t < 8.0e-3
    s = cost.sizes(CFG)
    assert s["always_params"] == (
        CFG["bytes"]["gqa_mixer_params"]
        + 3 * (CFG["bytes"]["kda_mixer_params"] - 2 * 8192 - 64 - 128)
        + 4 * CFG["bytes"]["shared_expert_and_router_params"])
    assert 0.18 < 2 * 3 * 48 * 4 * s["state_values"] / byts < 0.20
    assert 0.53 < 2 * 112 * s["expert_params"] / byts < 0.57
    # a wide step is still bound by its bytes
    ops, byts = cost.ops_and_bytes(CFG, 192, 48, 40 * 2500, 0, 41, 150, 160,
                                   192)
    assert ops / 197e12 < byts / 819e9


# ---- the reference against the program, through the engine ---------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmark.weights import make_weights
    config = arch.model_config(TINY)
    return make_weights(config, 4300000124), config


def test_seeded_weights_are_what_the_file_assumes(tiny_model):
    """``weights.py``, unedited, fills every new leaf with finite,
    non-degenerate values: gains and the correction bias 1, the decay's
    two vectors normal(0, 1) in float32, so that a state value about
    halves a token in the median."""
    lp = tiny_model[0]["layers"]["seg0"]
    assert list(lp) == ["full", "kda1", "kda2", "kda3"]
    for layer in lp.values():
        for name, leaf in layer.items():
            a = np.asarray(leaf, np.float32)
            assert np.isfinite(a).all(), name
            if name.endswith("norm"):
                assert float(abs(a - 1.0).max()) == 0.0
            else:
                assert a.std() > 0.01, name
    mix = lp["kda2"]
    assert str(mix["kda_A_log"].dtype) == str(
        mix["kda_dt_bias"].dtype) == "float32"
    a = np.exp(np.asarray(mix["kda_A_log"])[0, 0])             # (H,)
    step = np.log1p(np.exp(np.asarray(mix["kda_dt_bias"])[0, 0]))
    keep = np.exp(-np.repeat(a, 8) * step)
    assert 0.3 < float(np.median(keep)) < 0.7
    assert tiny_model[0]["lm_head"].shape == (64, 512)


def test_served_logps_agree_with_the_engine_and_the_control_does_not(
        tiny_model):
    """What ``correct.py`` compares on the chip, at test size: a group of
    three (one prefill; refcounts and state rows forked) and a lone
    request, sampled at temperature 1. float32 at ``highest`` on both
    sides: 1e-4, summation order (logits of magnitude ~5). The fp8 control
    rounds every matrix product's inputs."""
    from benchmark.reference import solar_open2 as ref
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
        assert np.abs(np.asarray(eng.result_logps(rid)) - want).max() < 1e-4
        gaps.append(np.abs(low - want).mean())
    assert min(gaps) > 30 * 1e-4


# ---- the new reader and the new metrics' files ----------------------------

def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


def test_kda_step_roofline_reads_the_programs_steps(monkeypatch):
    spec = load_json(HERE, "layer_metrics", "kda_step_roofline.rollout.json")
    assert spec["reader"] == "kda_step_roofline"
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    steps = [span("engine.step", used=48, ssm_rows=48, kda_chunk_entries=0,
                  experts_touched=112, local_pairs=230, kv_blocks_saved=2600,
                  block_size=32),
             span("engine.step", used=190, ssm_rows=47, kda_chunk_entries=144,
                  experts_touched=160, local_pairs=900),  # collected later
             span("engine.step"),                    # a step with no plan
             span("engine.emit", used=1, ssm_rows=1, kda_chunk_entries=1,
                  experts_touched=1, local_pairs=1)]
    host = [{"decode": 48, "sampled": 48, "contexts": 120000},
            {"decode": 46, "sampled": 47, "contexts": 110000}]
    r = types.SimpleNamespace(
        config_file=CFG, peaks=peaks, traced_steps=host,
        trace=types.SimpleNamespace(modules={
            "jit__paged_fused_step(123)": [(0, 12e6), (1, 40e6)],
            "jit_copy_state_rows": [(2, 1e6)]}))
    monkeypatch.setattr(kda_step_roofline, "recorded", lambda r: steps)
    least = (cost.least_seconds(CFG, peaks, 48, 48, 120000, 2600 * 32, 48,
                                0, 112, 230)
             + cost.least_seconds(CFG, peaks, 190, 47, 110000, 0, 47, 144,
                                  160, 900))
    got = kda_step_roofline.read(r, spec["args"])
    assert got == pytest.approx(100.0 * least / 52e-3) and 20 < got < 100
    # the parent's spans carry no such attr; another configuration; no
    # trace: left out, nothing raises
    monkeypatch.setattr(kda_step_roofline, "recorded",
                        lambda r: [span("engine.step", used=48)])
    assert kda_step_roofline.read(r, spec["args"]) is None
    monkeypatch.setattr(kda_step_roofline, "recorded", lambda r: steps)
    r.config_file = load_json(HERE, "configs", "qwen2.5-coder-1.5b.json")
    assert kda_step_roofline.read(r, spec["args"]) is None
    r.config_file, r.trace = CFG, None
    assert kda_step_roofline.read(r, spec["args"]) is None


def test_the_chunk_share_and_the_readout_read_the_steps_attrs(monkeypatch):
    steps = [span("engine.step", used=48, kda_chunk_entries=0,
                  kda_readout_absmax=1.5),
             span("engine.step", used=192, kda_chunk_entries=144,
                  kda_readout_absmax=3.25),
             span("engine.step"),
             span("engine.plan", used=9, kda_chunk_entries=9,
                  kda_readout_absmax=99.0)]
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: steps)
    monkeypatch.setattr(program_span_attr, "recorded", lambda r: steps)
    spec = load_json(HERE, "layer_metrics",
                     "kda_chunk_entry_share.rollout.json")
    assert spec["reader"] == "program_span_ratio"
    assert program_span_ratio.read(None, spec["args"]) == 100.0 * 144 / 240
    spec_max = load_json(HERE, "layer_metrics",
                         "kda_readout_absmax.rollout.json")
    assert spec_max["reader"] == "program_span_attr"
    assert program_span_attr.read(None, spec_max["args"]) == 3.25
    # a program from before the attrs: nothing to read, nothing raised
    old = [span("engine.step", used=48)]
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: old)
    monkeypatch.setattr(program_span_attr, "recorded", lambda r: old)
    assert program_span_ratio.read(None, spec["args"]) is None
    assert program_span_attr.read(None, spec_max["args"]) is None


# ---- the manifest: this PR's entries, by name ------------------------------

NEW_METRICS = {"kda_step_roofline.rollout": ("device_trace", "%"),
               "kda_chunk_entry_share.rollout": ("program_counter", "%"),
               "kda_readout_absmax.rollout": ("program_counter", "ratio")}
JOINED = ["rollout_tok_s", "fused_step_ms.rollout",
          "device_idle_share.rollout", "hbm_peak_share.rollout",
          "idle_inside_programs_share.rollout",
          "engine_unqueued_share.rollout", "run_ahead_share.rollout",
          "attn_shared_block_share.rollout", "head_entry_share.rollout",
          "moe_experts_touched.rollout", "moe_local_pairs_per_bank.rollout",
          "ssm_state_copies_max.rollout"]


def test_the_new_cell_reports_what_the_issue_lists():
    doc = load_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "grpo-rollout-ctx4k", 1)
    assert len(cell["why"]) <= 200
    man = Manifest(CELL)
    assert {"rollout_tok_s", "setup_s"} <= {m["name"]
                                            for m in man.end_to_end()}
    reported = {m["name"] for m in man.per_layer()}
    assert set(NEW_METRICS) | set(JOINED[1:]) | {
        "setup_compile_s", "window_compiles"} <= reported
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name, (source, unit) in NEW_METRICS.items():
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert (m["layer"], m["source"], m["unit"]) == ("fused step", source,
                                                        unit)
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           name + ".json"))
    for name in JOINED:
        assert CELL in metrics[name]["workloads"]
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
    model): the readers find no delta rule, and say nothing."""
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
