"""What PR 37 brings for ``longcat-flash-chat``: the configuration file
against the catalog's published keys and a recount of its bytes from the
program's own shapes, the architecture map and its refusals, the step's cost
on hand-counted sizes, the reference against the program through the engine
at a small size and its control, the new reader and the two data-only metrics
on hand-made records, and the manifest's new entries, by name."""

import functools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.archs import longcat_flash as arch
from benchmark.costs import fused_step_scmoe as cost
from benchmark.manifest import HERE, REHEARSAL, ROOT, Manifest, load_json
from benchmark.readers import (mla_moe_step_roofline, program_span_attr_ratio,
                               program_span_ratio, scmoe_step_roofline)

CELL = "longcat-flash-grpo-rollout-ctx4k"
GLM_CELL = "glm4.7-flash-grpo-rollout-ctx4k"
CFG = load_json(HERE, "configs", "longcat-flash-chat.json")
# the catalog row's ``config``: the model's own public config.json
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
# the same block at test size: 8 of 16 experts held, from the 4th
TINY = dict(
    PUBLISHED, name="tiny-longcat-flash", model_type="longcat_flash",
    reference="longcat_flash", vocab_size=512, hidden_size=64,
    ffn_hidden_size=96, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, kv_lora_rank=24, q_lora_rank=16,
    qk_rope_head_dim=4, v_head_dim=16, qk_nope_head_dim=8,
    n_routed_experts=8, max_position_embeddings=128, zero_expert_num=8,
    moe_topk=4, torch_dtype="float32", matmul_precision="highest",
    held_experts={"first": 4, "count": 8, "of": 16},
    published={"n_routed_experts": 16})


def test_configuration_file_is_the_published_one_cut_to_the_chips_share():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == {"num_layers", "n_routed_experts",
                       "vocab_size"} == set(CFG["reduced"])
    assert {k: CFG["published"][k] for k in changed} == {
        k: PUBLISHED[k] for k in changed}
    assert (CFG["num_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (4, 16, 16384)
    assert CFG["held_experts"] == {"first": 0, "count": 16, "of": 512}
    assert CFG["reference"] == "longcat_flash"
    assert {"assumed", "deployment", "kept", "bytes", "published"} <= set(CFG)
    assert {"norm_topk_prob", "tie_word_embeddings", "hidden_act",
            "router_bias", "torch_dtype", "rotary_pairing", "cached_latent",
            "correction_bias"} <= set(CFG["assumed"])
    assert "32 chips share each layer" in CFG["deployment"]
    entry = Manifest(CELL).config_entry
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the floors: a whole period (one layer) and at least four layers, at
    # least 8 experts held, at least an eighth of the vocabulary
    assert CFG["num_layers"] >= 4 and CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_files_bytes_are_a_recount_from_the_programs_shapes():
    """``bytes`` against ``jax.eval_shape`` of the program's own
    ``init_params`` and ``init_paged_pool``: nothing is allocated."""
    import jax
    from senweaver_ide_tpu.models import init_params
    from senweaver_ide_tpu.rollout.paged_kv import (init_paged_pool,
                                                    kv_row_bytes,
                                                    resolve_block_size)
    c = arch.model_config(CFG)
    tree = jax.eval_shape(functools.partial(init_params, c),
                          jax.random.PRNGKey(0))
    layers = tree["layers"]
    n = lambda *names, of=layers: sum(int(of[k].size) for k in names) // 4
    b = CFG["bytes"]
    for sub in (layers["sub0"], layers["sub1"]):
        assert b["attention_params_per_sublayer"] == n(
            "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", of=sub)
        assert b["dense_ffn_params"] == n("w_gate", "w_up", "w_down", of=sub)
    assert b["router_params"] == n("router") == 6144 * 768
    assert b["held_expert_params_per_layer"] == n(
        "w_gate", "w_up", "w_down") == 16 * b["expert_params"]
    assert b["layer_params_outside_routed"] == (
        2 * b["attention_params_per_sublayer"] + 2 * b["dense_ffn_params"]
        + b["router_params"]) == 638_844_928
    assert b["layer_params"] == (b["layer_params_outside_routed"]
                                 + b["held_expert_params_per_layer"])
    assert b["embedding_and_head_params"] == (
        int(tree["embed"].size) + int(tree["lm_head"].size))
    # every leaf but the norms' gains and the correction bias (0.4 MB)
    matrices = [a for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]
                if not str(path[-1].key).endswith("norm")]
    assert b["weights_bf16_bytes"] == 2 * (
        4 * b["layer_params"] + b["embedding_and_head_params"]) == sum(
            int(a.size) * a.dtype.itemsize for a in matrices)
    bs = resolve_block_size(kv_row_bytes(c), 4096)
    pool = jax.eval_shape(lambda: init_paged_pool(c, 52 * 4096 // bs, bs))
    assert pool.k.shape[0] == b["attention_layers_in_pool"] == 8
    assert b["latent_cache_bytes_per_token_per_sublayer"] == (
        pool.k.shape[-1] * 2) == kv_row_bytes(c)
    assert b["latent_cache_bytes"] == int(pool.k.size) * 2
    share = (b["weights_bf16_bytes"] + b["latent_cache_bytes"]) / 17.18e9
    assert 0.72 < share < 0.74
    # and the cost file counts the same matrices
    s = cost.sizes(CFG)
    assert s["attn_params"] == b["attention_params_per_sublayer"]
    assert s["dense_ffn_params"] == b["dense_ffn_params"]
    assert s["expert_params"] == b["expert_params"]
    assert s["router_params"] == b["router_params"]
    assert 2 * s["head_params"] == b["embedding_and_head_params"]


UNMAPPED = [
    ("zero_expert_type", "copy"), ("attention_bias", True),
    ("attention_method", "GQA"), ("rope_scaling", {"factor": 10}),
    ("index_topk", 2048), ("mtp_num_layers", 3), ("ngram_vocab_size_ratio", 78),
    ("held_experts", {"first": 0, "count": 8, "of": 512}),
    ("held_experts", {"first": 0, "count": 16, "of": 256})]


@pytest.mark.parametrize("key,value", UNMAPPED,
                         ids=[f"{k}-{i}" for i, (k, _) in enumerate(UNMAPPED)])
def test_arch_map_raises_on_what_it_does_not_map(key, value):
    with pytest.raises(SystemExit, match=key):
        arch.model_config(dict(CFG, **{key: value}))


def test_arch_map_gives_the_programs_config():
    c = arch.model_config(CFG)
    assert (c.num_layers, c.attn_layers, c.num_heads, c.head_dim) == (
        4, 8, 64, 192)
    assert (c.num_experts, c.routed_experts, c.moe_first_expert,
            c.moe_zero_experts, c.router_width, c.num_experts_per_tok) == (
        16, 512, 0, 256, 768, 12)
    assert (c.intermediate_size, c.expert_size, c.vocab_size) == (
        12288, 2048, 16384)
    assert c.shortcut_moe and c.expert_share and c.mla
    assert c.router_type == "softmax_bias" and c.routed_scaling_factor == 6.0
    assert c.mla_scales == (2.0, 12 ** 0.5)
    assert (c.latent_dim, c.latent_row_dim) == (576, 640)
    assert not c.tie_word_embeddings and c.rope_scaling is None
    assert c.rope_theta == 1e7 and c.first_dense_layers == 0


def test_step_cost_by_hand():
    """One small shape by hand: 2 layers of hidden 8, dense FFN 16, experts
    of 4 (3 held of 10, 5 identity), 2 heads of nope 3 + rope 2, value 4,
    ranks 5 and 6, 100 ids; then ISSUE 37's narrow step at the published
    widths."""
    small = {"hidden_size": 8, "ffn_hidden_size": 16,
             "expert_ffn_hidden_size": 4, "num_attention_heads": 2,
             "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 4,
             "q_lora_rank": 5, "kv_lora_rank": 6, "num_layers": 2,
             "n_routed_experts": 3, "zero_expert_num": 5, "vocab_size": 100,
             "held_experts": {"first": 0, "count": 3, "of": 10}}
    s = cost.sizes(small)
    assert s["attn_params"] == (8 * 5 + 5 * 2 * 5 + 8 * 8 + 6 * 2 * 7
                                + 2 * 4 * 8) == 302
    assert (s["dense_ffn_params"], s["expert_params"],
            s["router_params"]) == (384, 96, 8 * 15)
    always = 2 * (2 * 302 + 2 * 384 + 120)
    # 7 tokens, 3 sampled, decoding rows hold 50 tokens of cache, 2 banks
    # touched by 5 pairs
    ops, byts = cost.ops_and_bytes(small, 7, 3, 50, 2, 5)
    assert ops == (2 * always * 7 + 2 * 96 * 5 + 2 * 800 * 3
                   + 4 * 2 * 2 * (8 + 6) * 50)
    assert byts == (2 * (always + 800 + 2 * 96) + 2 * 8 * 4 * (50 + 7)
                    + 3 * 2 * 8 * 7)
    # an identity or absent pick costs nothing; a bank not touched no byte
    assert cost.ops_and_bytes(small, 7, 3, 50, 2, 0)[0] == ops - 2 * 96 * 5
    assert cost.ops_and_bytes(small, 7, 3, 50, 0, 5)[1] == byts - 2 * 2 * 96
    # the narrow step of the issue: 48 rows at ~2.5k tokens, 8.6 banks a
    # layer touched by 12 pairs a layer: >= 9 GB, bound by its bytes
    ops, byts = cost.ops_and_bytes(CFG, 48, 48, 48 * 2500, 4 * 8.6, 48)
    assert 9.0e9 < byts < 9.6e9 and ops / 197e12 < byts / 819e9
    # a wide step is still bound by its bytes
    ops, byts = cost.ops_and_bytes(CFG, 192, 48, 40 * 2500, 64, 192)
    assert 0.9e12 < ops < 1.2e12 and ops / 197e12 < byts / 819e9


# ---- the reference against the program, through the engine ---------------

@pytest.fixture(scope="module")
def tiny_model():
    from benchmark.weights import make_weights
    config = arch.model_config(TINY)
    return make_weights(config, 3700000124), config


def test_seeded_weights_are_what_the_file_assumes(tiny_model):
    lp = tiny_model[0]["layers"]
    # the correction bias and every norm a constant, every matrix drawn
    assert float(abs(np.asarray(lp["router_bias_norm"]) - 1.0).max()) == 0.0
    assert lp["router_bias_norm"].shape == (2, 24)
    drawn = [(lp, "router", 64), (lp, "w_down", 32)]
    for sub in (lp["sub0"], lp["sub1"]):
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm"):
            assert float(abs(np.asarray(sub[name]) - 1.0).max()) == 0.0
        drawn += [(sub, "w_gate", 64), (sub, "w_down", 96),
                  (sub, "wq_b", 16), (sub, "wkv_b", 24)]
    for of, name, fan_in in drawn:
        std = float(np.asarray(of[name], np.float32).std())
        assert 0.8 < std * fan_in ** 0.5 < 1.2, name
    assert float(abs(np.asarray(lp["sub0"]["wq_b"], np.float32)
                     - np.asarray(lp["sub1"]["wq_b"], np.float32)).max()) > 0


def test_served_logps_agree_with_the_engine_and_the_control_does_not(
        tiny_model):
    """What ``correct.py`` compares on the chip, at test size: a group of
    three (one prefill, forked) and a lone request, sampled at temperature
    1. float32 at ``highest`` on both sides: 3e-5, summation order
    (absorbed against expanded attention, sorted against dense experts).
    The fp8 control rounds every matrix product's inputs."""
    from benchmark.reference import longcat_flash as ref
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


def test_the_references_heads_in_groups_are_all_the_heads(tiny_model,
                                                          monkeypatch):
    from benchmark.reference import longcat_flash as ref
    params, _ = tiny_model
    toks = np.arange(40, dtype=np.int32)[None] * 11 % 512
    want = np.asarray(ref.logits(params, TINY, toks))
    monkeypatch.setattr(ref, "HEADS_AT_ONCE", 1)
    assert np.abs(np.asarray(ref.logits(params, TINY, toks))
                  - want).max() < 1e-5


# ---- the new reader, and the two metrics that are data alone --------------

def span(name, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs)


STEPS = [span("engine.step", used=48, experts_touched=34, expert_banks=64,
              expert_assignments=576, expert_picks=2304, zero_picks=770,
              local_pairs=48),
         span("engine.step", used=190, experts_touched=64, expert_banks=64,
              expert_assignments=2280, expert_picks=9120, zero_picks=3040,
              local_pairs=192),
         span("engine.step"),                  # a step with no plan
         span("engine.emit", used=1, experts_touched=1, local_pairs=1)]


def test_scmoe_step_roofline_reads_the_programs_counts(monkeypatch):
    spec = load_json(HERE, "layer_metrics",
                     "scmoe_step_roofline.rollout.json")
    assert spec["reader"] == "scmoe_step_roofline"
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    host = [{"decode": 48, "sampled": 48, "contexts": 120000},
            {"decode": 46, "sampled": 47, "contexts": 110000}]
    r = types.SimpleNamespace(
        config_file=CFG, peaks=peaks, traced_steps=host,
        trace=types.SimpleNamespace(modules={
            "jit__paged_fused_step(123)": [(0, 16e6), (1, 22e6)],
            "jit_copy_blocks": [(2, 1e6)]}))
    monkeypatch.setattr(scmoe_step_roofline, "recorded", lambda r: STEPS)
    least = (cost.least_seconds(CFG, peaks, 48, 48, 120000, 34, 48)
             + cost.least_seconds(CFG, peaks, 190, 47, 110000, 64, 192))
    got = scmoe_step_roofline.read(r, spec["args"])
    assert got == pytest.approx(100.0 * least / 38e-3) and 50 < got < 75
    # the glm reader takes this file's keys for its own and finds no
    # moe_intermediate_size: the cell is not on that metric's list
    monkeypatch.setattr(mla_moe_step_roofline, "recorded", lambda r: STEPS)
    with pytest.raises(KeyError, match="moe_intermediate_size|num_hidden"):
        mla_moe_step_roofline.read(r, spec["args"])
    # the parent's spans carry no such attr; another configuration; no
    # trace: left out, nothing raises
    monkeypatch.setattr(
        scmoe_step_roofline, "recorded",
        lambda r: [span("engine.step", used=48, experts_touched=3)])
    assert scmoe_step_roofline.read(r, spec["args"]) is None
    monkeypatch.setattr(scmoe_step_roofline, "recorded", lambda r: STEPS)
    for other in (load_json(HERE, "configs", "glm-4.7-flash.json"),
                  load_json(REHEARSAL, "tiny-test.json")):
        r.config_file = other
        assert scmoe_step_roofline.read(r, spec["args"]) is None
    r.config_file, r.trace = CFG, None
    assert scmoe_step_roofline.read(r, spec["args"]) is None


def test_zero_pick_share_and_pairs_a_bank_are_ratios_of_the_steps_attrs(
        monkeypatch):
    share = load_json(HERE, "layer_metrics",
                      "moe_zero_pick_share.rollout.json")
    assert share["reader"] == "program_span_ratio"
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: STEPS)
    assert program_span_ratio.read(None, share["args"]) == pytest.approx(
        100.0 * (770 + 3040) / (2304 + 9120))
    per_bank = load_json(HERE, "layer_metrics",
                         "moe_local_pairs_per_bank.rollout.json")
    assert per_bank["reader"] == "program_span_attr_ratio"
    monkeypatch.setattr(program_span_attr_ratio, "recorded", lambda r: STEPS)
    assert program_span_attr_ratio.read(
        None, per_bank["args"]) == pytest.approx((0.75 + 3.0) / 2)
    # a program from before the attrs: nothing to read, nothing raised
    old = [span("engine.step", used=48, experts_touched=34, expert_banks=64,
                expert_assignments=576)]
    monkeypatch.setattr(program_span_ratio, "recorded", lambda r: old)
    monkeypatch.setattr(program_span_attr_ratio, "recorded", lambda r: old)
    assert program_span_ratio.read(None, share["args"]) is None
    assert program_span_attr_ratio.read(None, per_bank["args"]) is None


# ---- the manifest, by name -------------------------------------------------

def test_the_new_cell_reports_what_the_issue_lists():
    doc = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"]: c for c in doc["configs"]}
    assert cells[CELL]["config"] == "longcat-flash-chat" in configs
    assert cells[CELL]["chips"] == 1
    assert configs["longcat-flash-chat"]["file"] == (
        "benchmark/configs/longcat-flash-chat.json")
    man = Manifest(CELL)
    assert man.cell["traffic"] == Manifest(GLM_CELL).cell["traffic"]
    assert [m["name"] for m in man.end_to_end()] == ["rollout_tok_s",
                                                     "setup_s"]
    assert {m["name"] for m in man.per_layer()} == {
        "setup_compile_s", "window_compiles", "fused_step_ms.rollout",
        "device_idle_share.rollout", "hbm_peak_share.rollout",
        "idle_inside_programs_share.rollout",
        "engine_unqueued_share.rollout", "run_ahead_share.rollout",
        "moe_experts_touched.rollout", "scmoe_step_roofline.rollout",
        "moe_zero_pick_share.rollout", "moe_local_pairs_per_bank.rollout"}
    new = {m["name"]: m for m in doc["per_layer"] if m["name"] in (
        "scmoe_step_roofline.rollout", "moe_zero_pick_share.rollout",
        "moe_local_pairs_per_bank.rollout")}
    assert len(new) == 3
    for m in new.values():
        assert m["workloads"] == [CELL] and m["moves"] == "rollout_tok_s"
        assert m["layer"] == "fused step" and m["source"] != "program_span"
    assert new["scmoe_step_roofline.rollout"]["unit"] == "%"
    # not on the lists the issue keeps it off
    for m in doc["per_layer"]:
        if m["name"].startswith(("idle_gap_", "engine_host_ms_per_step",
                                 "moe_expert_load_peak",
                                 "mla_moe_step_roofline",
                                 "fused_step_roofline")):
            assert CELL not in m["workloads"], m["name"]
    limits = man.limits
    assert (0 < limits["served_logp_gap_mean"]
            < limits["served_logp_gap_max"])


def test_rehearsal_of_the_new_cell_leaves_the_model_metrics_out():
    """The cell's control flow on the CPU at tiny-test sizes (a plain dense
    model): the readers find no expert layer, and say nothing."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--trace-seconds", "1"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert {"device_idle_share.rollout", "run_ahead_share.rollout",
            "window_compiles"} <= set(line["rehearsal"])
    assert not any(n.startswith(("scmoe_", "moe_"))
                   for n in line["rehearsal"])
