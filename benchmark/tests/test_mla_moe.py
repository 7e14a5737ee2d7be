"""What PR 26 brings for ``glm-4.7-flash``: the configuration file against
the published keys, the architecture map's refusals, the cost of a fused
step on hand-counted sizes, the readers on hand-made records and at
``tiny-test`` (None), and the reference's control."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import glm4_moe_lite as arch
from benchmark.costs import fused_step_mla_moe as cost
from benchmark.manifest import HERE, REHEARSAL, ROOT, Manifest, load_json
from benchmark.readers import (mla_moe_step_roofline, program_span_attr_ratio,
                               program_span_ratio)

CELL = "glm4.7-flash-grpo-rollout-ctx4k"
CFG = load_json(HERE, "configs", "glm-4.7-flash.json")
# the model's own public config.json, the keys that say something of its shape
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_configuration_file_is_the_published_one_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == {"num_hidden_layers"} == set(CFG["reduced"])
    assert CFG["num_hidden_layers"] == 7
    assert CFG["published"]["num_hidden_layers"] == 47
    assert CFG["reference"] == "glm4_moe_lite"
    assert {"assumed", "deployment", "kept", "bytes"} <= set(CFG)
    entry = Manifest(CELL).config_entry
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]


UNMAPPED = [("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
            ("n_group", 8), ("topk_group", 4),
            ("partial_rotary_factor", 0.5), ("topk_method", "greedy"),
            ("norm_topk_prob", False), ("attention_bias", True)]


@pytest.mark.parametrize("key,value", UNMAPPED, ids=[k for k, _ in UNMAPPED])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.num_layers, c.first_dense_layers, c.num_expert_layers) == (
        7, 1, 6)
    assert (c.head_dim, c.v_head_dim, c.latent_dim, c.latent_row_dim) == (
        256, 256, 576, 640)
    assert (c.num_experts, c.num_experts_per_tok, c.expert_size,
            c.num_shared_experts) == (64, 4, 1536, 1)
    assert c.router_type == "sigmoid_bias" and c.routed_scaling_factor == 1.8
    assert c.mla and not c.tie_word_embeddings


def test_step_cost_by_hand():
    """ISSUE 26's parameter counts."""
    s = cost.sizes(CFG)
    q_a, q_b = 2048 * 768, 768 * 20 * 256
    kv_a, kv_b, o = 2048 * 576, 512 * 20 * 448, 20 * 256 * 2048
    assert (q_a, q_b, kv_a, kv_b, o) == (1_572_864, 3_932_160, 1_179_648,
                                         4_587_520, 10_485_760)
    assert s["attn_params"] == q_a + q_b + kv_a + kv_b + o == 21_757_952
    assert s["expert_params"] == s["shared_params"] == 3 * 2048 * 1536
    assert s["router_params"] == 131_072
    assert s["dense_ffn_params"] == 62_914_560
    assert s["head_params"] == 154880 * 2048
    # an expert layer outside its routed experts, and whole; the dense layer
    outside = s["attn_params"] + s["shared_params"] + s["router_params"]
    assert outside == 31_326_208 == CFG["bytes"][
        "expert_layer_params_outside_routed"]
    assert 64 * s["expert_params"] == 603_979_776
    assert s["attn_params"] + s["dense_ffn_params"] == 84_672_512
    weights = 2 * (2 * s["head_params"] + 84_672_512
                   + 6 * (outside + 603_979_776))
    assert abs(weights - CFG["bytes"]["weights_bf16_bytes"]) < 5e6
    assert CFG["bytes"]["latent_cache_bytes"] == 52 * 4096 * 7 * 1152
    # 48 decode rows at 2500 tokens of context, 365 of 384 banks touched
    ops, byts = cost.ops_and_bytes(CFG, tokens=48, sampled=48,
                                   contexts=48 * 2500, experts_touched=365)
    always = (7 * 21_757_952 + 62_914_560 + 6 * (131_072 + 9_437_184))
    assert ops == (2 * (always + 6 * 4 * 9_437_184) * 48
                   + 2 * 154880 * 2048 * 48
                   + 7 * 2 * 20 * (576 + 512) * 48 * 2500)
    assert byts == (2 * (always + 154880 * 2048 + 365 * 9_437_184)
                    + 2 * 576 * 7 * (48 * 2500 + 48) + 3 * 2 * 2048 * 48)
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    t = cost.least_seconds(CFG, peaks, 48, 48, 48 * 2500, 365)
    assert t == byts / 819e9          # bytes bound: the experts dominate
    assert 10.5e-3 < t < 11.5e-3


def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


STEPS = [span("engine.step", used=48, entries=48, experts_touched=365,
              expert_banks=384, expert_load_max=9, expert_assignments=192),
         span("engine.step", used=190, entries=192, experts_touched=384,
              expert_banks=384, expert_load_max=24, expert_assignments=760),
         span("engine.step", used=46, entries=48, experts_touched=350,
              expert_banks=384, expert_load_max=11, expert_assignments=184),
         span("engine.step"),                     # a step with no plan
         span("engine.plan", admitted=1)]


@pytest.fixture
def recorded(monkeypatch):
    for mod in (mla_moe_step_roofline, program_span_attr_ratio,
                program_span_ratio):
        monkeypatch.setattr(mod, "recorded", lambda r: STEPS)


def record(config_file, runs_ns=(24e6, 80e6, 25e6)):
    host = [{"decode": 48, "sampled": 48, "contexts": 120_000},
            {"decode": 40, "sampled": 41, "contexts": 100_000},
            {"decode": 46, "sampled": 46, "contexts": 118_000}]
    return types.SimpleNamespace(
        config_file=config_file, traced_steps=host,
        peaks=load_json(HERE, "peaks.json")["TPU v5 lite"],
        trace=types.SimpleNamespace(modules={
            "jit__paged_fused_step(123)": [(0, d) for d in runs_ns],
            "jit__threefry_split(1)": [(0, 4e3)]}))


def test_roofline_reader_on_a_hand_made_record(recorded):
    r = record(CFG)
    args = load_json(HERE, "layer_metrics",
                     "mla_moe_step_roofline.rollout.json")["args"]
    least = sum(cost.least_seconds(CFG, r.peaks, a.attrs["used"],
                                   h["sampled"], h["contexts"],
                                   a.attrs["experts_touched"])
                for a, h in zip(STEPS[:3], r.traced_steps))
    got = mla_moe_step_roofline.read(r, args)
    assert got == pytest.approx(100.0 * least / 0.129)
    assert 20.0 < got < 40.0
    # the trace held a run fewer than the host recorded: scaled, not summed
    two = record(CFG, runs_ns=(24e6, 80e6))
    assert mla_moe_step_roofline.read(two, args) == pytest.approx(
        100.0 * least * (2 / 3) / 0.104)
    assert mla_moe_step_roofline.read(record(CFG, runs_ns=()), args) is None


def test_new_readers_read_none_at_tiny_test(recorded):
    tiny = load_json(REHEARSAL, "tiny-test.json")
    for name in ("mla_moe_step_roofline.rollout",
                 "moe_expert_load_peak.rollout"):
        spec = load_json(HERE, "layer_metrics", name + ".json")
        mod = {"mla_moe_step_roofline": mla_moe_step_roofline,
               "program_span_attr_ratio": program_span_attr_ratio}[
                   spec["reader"]]
        assert mod.read(record(tiny), spec["args"]) is None


def test_load_peak_and_touched_share(recorded):
    spec = load_json(HERE, "layer_metrics",
                     "moe_expert_load_peak.rollout.json")
    got = program_span_attr_ratio.read(record(CFG), spec["args"])
    assert got == pytest.approx(64 * np.median([9 / 192, 24 / 760,
                                                11 / 184]))
    spec = load_json(HERE, "layer_metrics",
                     "moe_experts_touched.rollout.json")
    assert spec["reader"] == "program_span_ratio"
    assert program_span_ratio.read(record(CFG), spec["args"]) == (
        pytest.approx(100.0 * (365 + 384 + 350) / (3 * 384)))


def test_a_program_without_the_attrs_reads_none(monkeypatch):
    """The parent commit's spans carry no routing attrs: the metric is
    left out of its line and nothing raises."""
    old = [span("engine.step", used=48, entries=48)]
    for mod in (mla_moe_step_roofline, program_span_attr_ratio,
                program_span_ratio):
        monkeypatch.setattr(mod, "recorded", lambda r: old)
    for name in ("mla_moe_step_roofline.rollout",
                 "moe_experts_touched.rollout",
                 "moe_expert_load_peak.rollout"):
        spec = load_json(HERE, "layer_metrics", name + ".json")
        mod = __import__("benchmark.readers." + spec["reader"],
                         fromlist=["read"])
        assert mod.read(record(CFG), spec["args"]) is None


def test_new_metrics_are_the_new_cells_alone():
    doc = load_json(ROOT, "BENCHMARK.json")
    new = {m["name"]: m for m in doc["per_layer"]
           if m["name"].startswith(("mla_moe_", "moe_"))}
    assert set(new) == {"mla_moe_step_roofline.rollout",
                        "moe_experts_touched.rollout",
                        "moe_expert_load_peak.rollout"}
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert m["layer"] == "fused step"
    reported = {m["name"] for m in Manifest(CELL).per_layer()}
    assert set(new) | {"fused_step_ms.rollout", "hbm_peak_share.rollout",
                       "engine_host_ms_per_step.rollout",
                       "device_idle_share.rollout", "setup_compile_s",
                       "window_compiles"} == reported
    assert [m["name"] for m in Manifest(CELL).end_to_end()] == [
        "rollout_tok_s", "setup_s"]


def test_rehearsal_of_the_new_cell_leaves_the_model_metrics_out():
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
    assert not any(n.startswith(("mla_moe_", "moe_"))
                   for n in line["rehearsal"])


def test_reference_control_moves_the_log_p():
    """``quant`` rounds every matrix product's inputs but the router's:
    the control has to read far from the reference itself."""
    import jax
    from benchmark.reference import glm4_moe_lite as ref
    from senweaver_ide_tpu.models import init_params
    tiny = dict(CFG, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=48, num_attention_heads=4,
                num_key_value_heads=4, n_routed_experts=8,
                num_experts_per_tok=2, num_hidden_layers=3, q_lora_rank=32,
                kv_lora_rank=24, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=16, vocab_size=512, max_position_embeddings=128,
                torch_dtype="float32", matmul_precision="highest",
                name="tiny-glm-moe-test")
    params = init_params(arch.model_config(tiny), jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                         512))
    starts = np.array([9, 19])
    base = np.asarray(ref.served_logps(params, tiny, toks, starts, 12))
    again = np.asarray(ref.served_logps(params, tiny, toks, starts, 12))
    low = np.asarray(ref.served_logps(params, tiny, toks, starts, 12,
                                      quant="fp8"))
    assert base.shape == (2, 12) and np.array_equal(base, again)
    assert np.abs(low - base).mean() > 100 * 2e-5
    m = ref.margins()
    assert m["pairs"] >= 2 * 2 * 512 and 0.0 <= m["under_0.001"] <= m[
        "under_0.01"] <= 1.0
