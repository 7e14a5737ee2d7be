"""What PR 32 brings for ``falcon-h1-34b-instruct``: the configuration file
against the catalog's published keys and a recount of its bytes, the
architecture map and its refusals, the step's cost on hand-counted sizes,
the reference against the program through the engine at a small size and
its control, the two new readers on hand-made records, and the manifest's
new entries."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import falcon_h1 as arch
from benchmark.costs import fused_step_hybrid_ssm as cost
from benchmark.manifest import HERE, REHEARSAL, ROOT, Manifest, load_json
from benchmark.readers import (program_span_attr, ssm_device_share,
                               ssm_step_roofline)

CELL = "falcon-h1-34b-grpo-rollout-ctx4k"
GLM_CELL = "glm4.7-flash-grpo-rollout-ctx4k"
CFG = load_json(HERE, "configs", "falcon-h1-34b-instruct.json")
# the catalog row's ``config``: the model's own public config.json
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}
# the same block at test size
TINY = dict(
    PUBLISHED, name="tiny-falcon-h1", hidden_size=64, intermediate_size=128,
    head_dim=16, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=2, max_position_embeddings=128, vocab_size=512,
    mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
    lm_head_multiplier=0.25, key_multiplier=0.7,
    torch_dtype="float32", matmul_precision="highest")


def test_configuration_file_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == {"num_hidden_layers"} == set(CFG["reduced"])
    assert CFG["num_hidden_layers"] == 5
    assert CFG["published"]["num_hidden_layers"] == 72
    assert CFG["reference"] == "falcon_h1"
    assert {"assumed", "deployment", "kept", "bytes", "published"} <= set(CFG)
    entry = Manifest(CELL).config_entry
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the floors: a whole period (one layer) and at least four layers, no
    # expert, the whole vocabulary
    assert CFG["num_hidden_layers"] >= 4


def test_the_files_bytes_are_a_recount_from_its_keys():
    d, f, v = CFG["hidden_size"], CFG["intermediate_size"], CFG["vocab_size"]
    hq, hkv, dh = (CFG["num_attention_heads"], CFG["num_key_value_heads"],
                   CFG["head_dim"])
    i, h, p, n, g, k = (CFG["mamba_d_ssm"], CFG["mamba_n_heads"],
                        CFG["mamba_d_head"], CFG["mamba_d_state"],
                        CFG["mamba_n_groups"], CFG["mamba_d_conv"])
    conv = i + 2 * g * n
    b = CFG["bytes"]
    assert b["attention_params_per_layer"] == (
        d * hq * dh + 2 * d * hkv * dh + hq * dh * d) == 31_457_280
    assert b["mixer_in_proj_params"] == d * (i + conv + h) == 47_349_760
    assert b["mixer_out_proj_params"] == i * d == 20_971_520
    assert b["mixer_small_params_per_layer"] == (k + 1) * conv + 3 * h + i
    assert b["mlp_params_per_layer"] == 3 * d * f == 330_301_440
    assert b["layer_params"] == 430_080_000
    assert b["embedding_and_head_params"] == 2 * v * d == 2_673_868_800
    layers = CFG["num_hidden_layers"]
    assert b["weights_bf16_bytes"] == 2 * (
        layers * b["layer_params"] + b["embedding_and_head_params"])
    assert b["kv_bytes_per_token_per_layer"] == 2 * hkv * dh * 2
    mix = load_json(HERE, "traffic", "grpo-rollout-ctx4k.json")["engine"]
    slots, max_len = mix["num_slots"], mix["max_len"]
    assert b["kv_pool_bytes"] == ((slots + 4) * max_len * layers
                                  * b["kv_bytes_per_token_per_layer"])
    assert b["state_bytes_per_row_per_layer"] == h * p * n * 4 == 4_194_304
    assert b["conv_window_bytes_per_row_per_layer"] == (k - 1) * conv * 2
    assert b["state_rows"] == slots + max(2, slots // 6) == 56
    assert b["state_pool_bytes"] == b["state_rows"] * layers * (
        b["state_bytes_per_row_per_layer"]
        + b["conv_window_bytes_per_row_per_layer"])
    share = (b["weights_bf16_bytes"] + b["kv_pool_bytes"]
             + b["state_pool_bytes"]) / 17.18e9
    assert 0.74 < share < 0.78
    # and the cost file counts the same matrices
    s = cost.sizes(CFG)
    assert s["layer_params"] == b["layer_params"]
    assert s["mixer_small_params"] == b["mixer_small_params_per_layer"]
    assert 2 * s["head_params"] == b["embedding_and_head_params"]


UNMAPPED = [
    ("attn_layer_indices", [0, 4]), ("rope_scaling", {"factor": 2.0}),
    ("attention_bias", True), ("mlp_bias", True), ("projectors_bias", True),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("mamba_use_mlp", False), ("hidden_act", "gelu"), ("mamba_d_ssm", 5120),
    ("ssm_multipliers", [1.0, 1.0]), ("mlp_multipliers", [1.0])]


@pytest.mark.parametrize("key,value", UNMAPPED,
                         ids=[k for k, _ in UNMAPPED])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.num_layers, c.num_heads, c.num_kv_heads, c.head_dim) == (
        5, 20, 4, 128)
    assert (c.mamba_d_ssm, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
            c.mamba_n_groups, c.mamba_d_conv) == (4096, 32, 128, 256, 2, 4)
    assert (c.ssm_conv_dim, c.ssm_proj_dim) == (5120, 9248)
    assert c.ssm and not c.mla and not c.tie_word_embeddings
    assert c.rope_theta == 1e11 and c.rope_scaling is None
    assert (c.embedding_multiplier, c.lm_head_multiplier,
            c.attention_in_multiplier, c.attention_out_multiplier,
            c.key_multiplier, c.ssm_in_multiplier, c.ssm_out_multiplier) == (
        5.656854249492381, 0.0078125, 1.0, 0.0375, 0.011048543456039804,
        0.25, 0.08838834764831845)
    assert c.ssm_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert c.mlp_multipliers == tuple(PUBLISHED["mlp_multipliers"])


def test_step_cost_by_hand():
    """One small shape by hand: 2 layers of hidden 8, MLP 16, 2 query / 1
    kv heads x 4, a mixer of 2 heads x 3 with a state of 5 in 1 group and
    a 4-tap conv, 100 ids; then ISSUE 32's narrow step at the published
    widths."""
    small = {"hidden_size": 8, "intermediate_size": 16,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "head_dim": 4, "mamba_d_ssm": 6, "mamba_n_heads": 2,
             "mamba_d_head": 3, "mamba_d_state": 5, "mamba_n_groups": 1,
             "mamba_d_conv": 4, "vocab_size": 100, "num_hidden_layers": 2}
    s = cost.sizes(small)
    conv = 6 + 2 * 5
    assert s["attn_params"] == 8 * 8 + 2 * 8 * 4 + 8 * 8 == 192
    assert s["mixer_params"] == 8 * (6 + conv + 2) + 6 * 8 == 240
    assert s["layer_params"] == 192 + 240 + 3 * 8 * 16 == 816
    assert (s["state_values"], s["window_values"]) == (2 * 3 * 5, 3 * conv)
    # 7 tokens, 3 sampled, decoding rows hold 50 tokens of KV, 4 rows moved
    ops, byts = cost.ops_and_bytes(small, 7, 3, 50, 4)
    assert ops == (2 * 2 * 816 * 7 + 2 * 800 * 3 + 2 * 4 * 2 * 4 * 50
                   + 2 * 4 * 30 * 7)
    assert byts == (2 * (2 * 816 + 800) + 2 * (2 * 1 * 4) * 2 * (50 + 7)
                    + 2 * 2 * 4 * (4 * 30 + 2 * 48) + 3 * 2 * 8 * 7)
    # rows the step did not advance cost nothing
    assert cost.ops_and_bytes(small, 7, 3, 50, 0)[1] == byts - 2 * 2 * 4 * (
        4 * 30 + 2 * 48)
    # 48 decode rows at 2500 tokens of context, every row's state moved:
    # 4.30 GB of layer weights + 2.67 GB of head + 2.01 GB of state + 1.23
    # GB of KV ~ 10.2 GB, 12.5 ms at 819 GB/s
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    ops, byts = cost.ops_and_bytes(CFG, 48, 48, 48 * 2500, 48)
    t = cost.least_seconds(CFG, peaks, 48, 48, 48 * 2500, 48)
    assert t == byts / 819e9 and 12.0e-3 < t < 13.0e-3
    state = 2 * 5 * 48 * (4_194_304 + 30_720)
    assert 0.19 < state / byts < 0.21
    # a wide step is still bound by its bytes: 192 x 2 x 2.15e9 + the head ~ 1 TFLOP
    ops, byts = cost.ops_and_bytes(CFG, 192, 48, 40 * 2500, 42)
    assert 0.9e12 < ops < 1.1e12 and ops / 197e12 < byts / 819e9


# ---- the reference against the program, through the engine ---------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmark.weights import make_weights
    config = arch.model_config(TINY)
    return make_weights(config, 3200000124), config


def test_seeded_weights_are_what_the_file_assumes(tiny_model):
    lp = tiny_model[0]["layers"]
    assert float(abs(lp["ssm_norm"] - 1.0).max()) == 0.0
    for name in ("ssm_A_log", "ssm_dt_bias", "ssm_D", "ssm_conv_b"):
        assert 0.1 < float(np.asarray(lp[name], np.float32).std()) < 1.5
    assert str(lp["ssm_A_log"].dtype) == "float32"


def test_served_logps_agree_with_the_engine_and_the_control_does_not(
        tiny_model):
    """What ``correct.py`` compares on the chip, at test size: a group of
    three (one prefill, forked by state copy) and a lone request, sampled
    at temperature 1. float32 at ``highest`` on both sides: 3e-5,
    summation order (the program's chunked scan, the reference's
    recurrence). The fp8 control rounds every matrix product's inputs."""
    from benchmark.reference import falcon_h1 as ref
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
    """The head and its log-sum-exp in vocabulary blocks of 128 (four
    blocks at this size) against the whole head's log-softmax."""
    from benchmark.reference import falcon_h1 as ref
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


# ---- the two new readers --------------------------------------------------

def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


def record(config_file, ops=None, busy_s=2.0):
    trace = None if ops is None else types.SimpleNamespace(ops=ops,
                                                           busy_s=busy_s)
    return types.SimpleNamespace(config_file=config_file, trace=trace)


OPS = {  # as trace_reduce prints them: name, then the result's shape
    "fusion.412_f32_5_56_32_128_256_": 0.400,      # the rows' states
    "fusion.77_bf16_48_1_9248_": 0.120,            # the input projection
    "fusion.80_bf16_48_32_128_": 0.010,            # x by head
    "fusion.81_bf16_192_2_256_": 0.006,            # B, C by group
    "fusion.82_f32_32_192_192_": 0.020,            # decays between entries
    "fusion.83_bf16_48_1_4096_": 0.030,            # the gated norm
    "fusion.84_bf16_5_56_3_5120_": 0.004,          # the conv's windows
    # not the mixer's: the MLP, the kernel, shapes that only look alike
    "fusion.5_bf16_48_1_21504_": 0.700,
    "paged_attention_rows.8_bf16_64_32_128_": 0.300,   # 20 heads padded
    "fusion.12_bf16_48_1_5120_": 0.050,            # as wide as the hidden
    "fusion.13_bf16_48_4_128_": 0.020,             # k by kv head
    "fusion.14_f32_48_32_": 0.020,                 # the step sizes
    "fusion.15_f32_20_192_192_": 0.020,
    "fusion.16_f32_48_261120_": 0.200}


def test_ssm_device_share_counts_the_mixers_shapes_alone():
    spec = load_json(HERE, "layer_metrics", "ssm_device_share.rollout.json")
    assert spec["reader"] == "ssm_device_share"
    got = ssm_device_share.read(record(CFG, OPS), spec["args"])
    assert got == pytest.approx(100.0 * 0.590 / 2.0)
    # no trace; a trace without such an operation (the parent's program);
    # a configuration without a mixer
    assert ssm_device_share.read(record(CFG), spec["args"]) is None
    plain = {k: v for k, v in OPS.items()
             if k.startswith(("fusion.1", "fusion.5_", "paged_"))}
    assert ssm_device_share.read(record(CFG, plain), spec["args"]) is None
    glm = load_json(HERE, "configs", "glm-4.7-flash.json")
    tiny = load_json(REHEARSAL, "tiny-test.json")
    for other in (glm, tiny):
        assert ssm_device_share.read(record(other, OPS), spec["args"]) is None


def test_ssm_step_roofline_reads_the_programs_rows(monkeypatch):
    spec = load_json(HERE, "layer_metrics", "ssm_step_roofline.rollout.json")
    assert spec["reader"] == "ssm_step_roofline"
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    steps = [span("engine.step", used=48, ssm_rows=48),
             span("engine.step", used=190, ssm_rows=47),
             span("engine.step"),                  # a step with no plan
             span("engine.emit", used=1, ssm_rows=1)]
    host = [{"decode": 48, "sampled": 48, "contexts": 120000},
            {"decode": 46, "sampled": 47, "contexts": 110000}]
    r = types.SimpleNamespace(
        config_file=CFG, peaks=peaks, traced_steps=host,
        trace=types.SimpleNamespace(modules={
            "jit__paged_fused_step(123)": [(0, 20e6), (1, 30e6)],
            "jit_copy_state_rows": [(2, 1e6)]}))
    monkeypatch.setattr(ssm_step_roofline, "recorded", lambda r: steps)
    least = (cost.least_seconds(CFG, peaks, 48, 48, 120000, 48)
             + cost.least_seconds(CFG, peaks, 190, 47, 110000, 47))
    got = ssm_step_roofline.read(r, spec["args"])
    assert got == pytest.approx(100.0 * least / 50e-3) and 40 < got < 60
    # the parent's spans carry no such attr; a configuration with no mixer;
    # no trace: left out, nothing raises
    monkeypatch.setattr(ssm_step_roofline, "recorded",
                        lambda r: [span("engine.step", used=48)])
    assert ssm_step_roofline.read(r, spec["args"]) is None
    monkeypatch.setattr(ssm_step_roofline, "recorded", lambda r: steps)
    r.config_file = load_json(HERE, "configs", "qwen2.5-coder-1.5b.json")
    assert ssm_step_roofline.read(r, spec["args"]) is None
    r.config_file, r.trace = CFG, None
    assert ssm_step_roofline.read(r, spec["args"]) is None


def test_ssm_state_copies_max_is_the_largest_attr(monkeypatch):
    spec = load_json(HERE, "layer_metrics",
                     "ssm_state_copies_max.rollout.json")
    assert spec["reader"] == "program_span_attr"
    steps = [span("engine.step", used=48, ssm_state_copies=0),
             span("engine.step", used=190, ssm_state_copies=8),
             span("engine.step"),
             span("engine.plan", ssm_state_copies=99)]
    monkeypatch.setattr(program_span_attr, "recorded", lambda r: steps)
    assert program_span_attr.read(None, spec["args"]) == 8.0
    monkeypatch.setattr(program_span_attr, "recorded",
                        lambda r: [span("engine.step", used=48)])
    assert program_span_attr.read(None, spec["args"]) is None


# ---- the manifest ---------------------------------------------------------

def test_the_new_cell_reports_what_the_issue_lists():
    doc = load_json(ROOT, "BENCHMARK.json")
    assert len(doc["workloads"]) == 5 and len(doc["configs"]) == 4
    assert doc["workloads"][-1]["name"] == CELL
    assert all(w["chips"] == 1 for w in doc["workloads"])
    man = Manifest(CELL)
    assert man.cell["traffic"] == Manifest(GLM_CELL).cell["traffic"]
    assert [m["name"] for m in man.end_to_end()] == ["rollout_tok_s",
                                                     "setup_s"]
    assert {m["name"] for m in man.per_layer()} == {
        "setup_compile_s", "window_compiles",
        "engine_host_ms_per_step.rollout", "fused_step_ms.rollout",
        "device_idle_share.rollout", "hbm_peak_share.rollout",
        "ssm_step_roofline.rollout", "ssm_device_share.rollout",
        "ssm_state_copies_max.rollout"}
    new = {m["name"]: m for m in doc["per_layer"]
           if m["name"].startswith("ssm_")}
    assert list(new) == [m["name"] for m in doc["per_layer"][-3:]]
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert m["layer"] == "fused step" and m["source"] != "program_span"
    # the shared metrics list the cell last; the dense roofline and the
    # program_span metrics do not list it
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    assert not any(CELL in m["workloads"] for m in doc["per_layer"]
                   if m["source"] == "program_span"
                   or m["name"].startswith("fused_step_roofline"))
    limits = man.limits
    assert (0 < limits["served_logp_gap_mean"]
            < limits["served_logp_gap_max"])


def test_rehearsal_of_the_new_cell_leaves_the_model_metrics_out():
    """The cell's control flow on the CPU at tiny-test sizes (a plain dense
    model): the readers find no mixer, and say nothing."""
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
    assert not any(n.startswith("ssm_") for n in line["rehearsal"])
