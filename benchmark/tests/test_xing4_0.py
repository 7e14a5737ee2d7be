"""What PR 30 brings for ``xing4.0-29b-a4b``: the configuration file against
the catalog's published keys, the architecture map and its refusals, the
step's cost on hand-counted sizes, the reference against the program
through the engine at a small size and its control, the two new readers on
hand-made records, and the manifest's new entries."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import xing4_0 as arch
from benchmark.costs import fused_step_mla_moe as cost
from benchmark.manifest import HERE, REHEARSAL, ROOT, Manifest, load_json
from benchmark.readers import mhc_device_share, program_span_attr

CELL = "xing4.0-grpo-rollout-ctx4k"
GLM_CELL = "glm4.7-flash-grpo-rollout-ctx4k"
CFG = load_json(HERE, "configs", "xing4.0-29b-a4b.json")
# the catalog row's ``config``: the model's own public config.json
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
# the same layer at test size (4 Sinkhorn rounds: see tiny_xing_mhc_test)
TINY = dict(
    PUBLISHED, name="tiny-xing-mhc-test", hidden_size=64,
    intermediate_size=160, max_position_embeddings=128,
    moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=4,
    n_routed_experts=8, num_experts_per_tok=2, num_hidden_layers=4,
    hc_sinkhorn_iters=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
    rope_scaling=dict(PUBLISHED["rope_scaling"], beta_fast=2, beta_slow=0.25,
                      factor=8, original_max_position_embeddings=16),
    torch_dtype="float32", matmul_precision="highest")


def test_configuration_file_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == {"num_hidden_layers"} == set(CFG["reduced"])
    assert CFG["num_hidden_layers"] == 7
    assert CFG["published"]["num_hidden_layers"] == 40
    assert CFG["reference"] == "xing4_0"
    assert {"assumed", "deployment", "kept", "bytes"} <= set(CFG)
    entry = Manifest(CELL).config_entry
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the floors: a whole period (one layer), both dense layers, at least
    # four expert layers, every expert, the whole vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4


UNMAPPED = [
    ("rope_scaling", None),
    ("rope_scaling", dict(PUBLISHED["rope_scaling"], type="linear")),
    ("rope_scaling", dict(PUBLISHED["rope_scaling"], truncate=False)),
    ("n_group", 8), ("topk_group", 4), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("norm_topk_prob", False),
    ("moe_layer_freq", 2), ("ep_size", 8), ("attention_bias", True),
    ("hc_mult", 0), ("num_key_value_heads", 8)]


@pytest.mark.parametrize("key,value", UNMAPPED,
                         ids=[f"{k}-{i}" for i, (k, _) in enumerate(UNMAPPED)])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.num_layers, c.first_dense_layers, c.num_expert_layers) == (
        7, 2, 5)
    assert (c.head_dim, c.v_head_dim, c.latent_dim, c.latent_row_dim) == (
        192, 128, 576, 640)
    assert (c.num_experts, c.num_experts_per_tok, c.expert_size,
            c.num_shared_experts) == (64, 4, 1024, 1)
    assert c.router_type == "sigmoid_bias" and c.routed_scaling_factor == 2.0
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_maps) == (
        4, 20, 1e-6, 24)
    assert (c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max) == (-30.0, 30.0)
    ys = c.rope_scaling
    assert (ys.factor, ys.original_max_position, ys.beta_fast, ys.beta_slow,
            ys.mscale, ys.mscale_all_dim) == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert c.attn_scale == pytest.approx(192 ** -0.5 * 1.41589 ** 2,
                                         rel=1e-5)
    assert c.mla and not c.tie_word_embeddings


def test_step_cost_by_hand():
    """ISSUE 30's parameter counts; ``costs/fused_step_mla_moe.py`` reads
    this configuration's keys as they are. The residual path is not in it:
    its two projections a layer are 7 x 688,128 float32 values = 19 MB
    beside ~8.4 GB of weights a narrow step reads, 0.2%."""
    s = cost.sizes(CFG)
    q_a, q_b = 3584 * 768, 768 * 32 * 192
    kv_a, kv_b, o = 3584 * 576, 512 * 32 * 256, 32 * 128 * 3584
    assert s["attn_params"] == q_a + q_b + kv_a + kv_b + o == 28_409_856
    assert s["expert_params"] == s["shared_params"] == 11_010_048
    assert s["router_params"] == 229_376
    assert s["dense_ffn_params"] == 99_090_432
    assert s["head_params"] == 131072 * 3584
    assert (s["layers"], s["dense_layers"], s["expert_layers"]) == (7, 2, 5)
    b = CFG["bytes"]
    assert b["attention_params_per_layer"] == s["attn_params"]
    assert b["routed_params_per_layer"] == 64 * s["expert_params"]
    assert b["mhc_projection_params_per_layer"] == 2 * (4 * 3584) * 24
    assert b["dense_layer_params"] == (
        s["attn_params"] + s["dense_ffn_params"] + 688_128) == 128_188_416
    assert b["expert_layer_params"] == (
        s["attn_params"] + 65 * s["expert_params"] + s["router_params"]
        + 688_128) == 744_980_480
    assert b["embedding_and_head_params"] == 2 * s["head_params"]
    assert b["weights_bf16_bytes"] == 2 * (
        2 * 128_188_416 + 5 * 744_980_480 + 2 * s["head_params"])
    assert b["latent_cache_bytes"] == 52 * 4096 * 7 * 640 * 2
    share = (b["weights_bf16_bytes"] + b["latent_cache_bytes"]) / 17.18e9
    assert 0.60 < share < 0.75
    # 48 decode rows at 2500 tokens of context, 300 of 320 banks touched
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    ops, byts = cost.ops_and_bytes(CFG, 48, 48, 48 * 2500, 300)
    t = cost.least_seconds(CFG, peaks, 48, 48, 48 * 2500, 300)
    assert t == byts / 819e9 and 9e-3 < t < 12e-3
    assert 7 * 2 * 688_128 * 4 / byts < 0.005


# ---- the reference against the program, through the engine ---------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmark.weights import make_weights
    config = arch.model_config(TINY)
    return make_weights(config, 2600000124), config


def test_served_logps_agree_with_the_engine_and_the_control_does_not(
        tiny_model):
    """What ``correct.py`` compares on the chip, at test size: a group of
    three and a lone request, sampled at temperature 1. float32 at
    ``highest`` on both sides: 3e-5, summation order. The fp8 control
    rounds every matrix product's inputs but the router's and the maps'."""
    from benchmark.reference import xing4_0 as ref
    from senweaver_ide_tpu.rollout import EngineConfig, RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams
    params, config = tiny_model
    eng = RolloutEngine(
        params, config, num_slots=4, max_len=64,
        sample=SampleParams(temperature=1.0, top_k=0, top_p=1.0),
        engine_config=EngineConfig(block_size=4, step_tokens=8))
    group = list(range(1, 14))
    rids = eng.submit_group(group, 3, max_new_tokens=9)
    lone = eng.submit([7, 7, 7], max_new_tokens=9)
    eng.run()
    assert eng.kv_layout == "paged" and eng.kv_layout_fallback is None
    gaps = []
    for p, rid in [(group, r) for r in rids] + [([7, 7, 7], lone)]:
        seq = np.asarray([p + eng.result(rid)], np.int32)
        want = np.asarray(ref.served_logps(params, TINY, seq, [len(p) - 1],
                                           9))[0]
        low = np.asarray(ref.served_logps(params, TINY, seq, [len(p) - 1],
                                          9, quant="fp8"))[0]
        assert np.abs(np.asarray(eng.result_logps(rid)) - want).max() < 3e-5
        gaps.append(np.abs(low - want).mean())
    assert min(gaps) > 100 * 3e-5


# ---- the two new readers --------------------------------------------------

def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


def record(config_file, ops=None, busy_s=2.0):
    trace = None if ops is None else types.SimpleNamespace(ops=ops,
                                                           busy_s=busy_s)
    return types.SimpleNamespace(config_file=config_file, trace=trace)


OPS = {  # as trace_reduce prints them: name, then the result's shape
    "pad_maximum_fusion.9_bf16_192_1_4_3584_": 0.050,    # the mixed stream
    "fusion.696_f32_48_1_24_": 0.030,                    # the projection
    "fusion.698_f32_48_1_16_": 0.010,                    # exp of the logits
    "copy.244_bf16_48_1_4_3584_": 0.004,
    "fusion.7_f32_48_14336_": 0.006,                     # flattened rows
    "fusion.8_f32_48_1_4_4_": 0.002,                     # H_res
    # not the path's: experts, the kernel, shapes that only look alike
    "ragged-dot.3_bf16_768_1024_": 0.900,
    "paged_latent_attention_rows.1_f32_192_32_640_": 0.300,
    "fusion.12_bf16_48_1_3584_": 0.020,
    "fusion.13_s32_48_16_": 0.020,
    "fusion.14_f32_48_4_": 0.020,
    "fusion.15_f32_24_": 0.020,
    "fusion.16_bf16_4_3584_": 0.020}


def test_mhc_device_share_counts_the_streams_shapes_alone():
    spec = load_json(HERE, "layer_metrics", "mhc_device_share.rollout.json")
    assert spec["reader"] == "mhc_device_share"
    got = mhc_device_share.read(record(CFG, OPS), spec["args"])
    assert got == pytest.approx(100.0 * 0.102 / 2.0)
    # no trace; a trace without such an operation (the parent's program);
    # a configuration without streams
    assert mhc_device_share.read(record(CFG), spec["args"]) is None
    plain = {k: v for k, v in OPS.items() if v >= 0.02 and "4_3584" not in k
             and "_24_" not in k}
    assert mhc_device_share.read(record(CFG, plain), spec["args"]) is None
    glm = load_json(HERE, "configs", "glm-4.7-flash.json")
    tiny = load_json(REHEARSAL, "tiny-test.json")
    for other in (glm, tiny):
        assert mhc_device_share.read(record(other, OPS), spec["args"]) is None


def test_mhc_sinkhorn_err_is_the_largest_attr(monkeypatch):
    spec = load_json(HERE, "layer_metrics", "mhc_sinkhorn_err.rollout.json")
    assert spec["reader"] == "program_span_attr"
    steps = [span("engine.step", used=48, mhc_ds_err=2e-5),
             span("engine.step", used=190, mhc_ds_err=7e-4),
             span("engine.step"),                 # a step with no plan
             span("engine.emit", mhc_ds_err=1.0)]
    monkeypatch.setattr(program_span_attr, "recorded", lambda r: steps)
    assert program_span_attr.read(None, spec["args"]) == 7e-4
    # the parent's spans carry no such attr: left out, nothing raises
    monkeypatch.setattr(program_span_attr, "recorded",
                        lambda r: [span("engine.step", used=48)])
    assert program_span_attr.read(None, spec["args"]) is None


# ---- the manifest ---------------------------------------------------------

def test_the_new_cell_reports_what_the_issue_lists():
    doc = load_json(ROOT, "BENCHMARK.json")
    assert len(doc["workloads"]) == 4 and len(doc["configs"]) == 3
    assert all(w["chips"] == 1 for w in doc["workloads"])
    man = Manifest(CELL)
    assert man.cell["traffic"] == Manifest(GLM_CELL).cell["traffic"]
    assert [m["name"] for m in man.end_to_end()] == ["rollout_tok_s",
                                                     "setup_s"]
    assert {m["name"] for m in man.per_layer()} == {
        "setup_compile_s", "window_compiles",
        "engine_host_ms_per_step.rollout", "fused_step_ms.rollout",
        "device_idle_share.rollout", "hbm_peak_share.rollout",
        "mla_moe_step_roofline.rollout", "moe_experts_touched.rollout",
        "moe_expert_load_peak.rollout", "mhc_device_share.rollout",
        "mhc_sinkhorn_err.rollout"}
    new = {m["name"]: m for m in doc["per_layer"]
           if m["name"].startswith("mhc_")}
    assert set(new) == {"mhc_device_share.rollout",
                        "mhc_sinkhorn_err.rollout"}
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert m["layer"] == "fused step" and m["source"] != "program_span"
    # the glm cell's three now list both cells, glm's first
    for m in doc["per_layer"]:
        if m["name"].startswith(("mla_moe_", "moe_")):
            assert m["workloads"] == [GLM_CELL, CELL]
    # the program_span metrics stay one cell each
    assert not any(CELL in m["workloads"] for m in doc["per_layer"]
                   if m["source"] == "program_span")


def test_rehearsal_of_the_new_cell_leaves_the_model_metrics_out():
    """The cell's control flow on the CPU at tiny-test sizes (a plain dense
    model): the readers find no stream and no expert, and say nothing."""
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
    assert not any(n.startswith(("mhc_", "mla_moe_", "moe_"))
                   for n in line["rehearsal"])
