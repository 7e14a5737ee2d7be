"""costs/fused_step.py against a hand count at one shape."""

from benchmark.costs import fused_step
from benchmark.manifest import HERE, load_json


def test_qwen_decode_step_by_hand():
    cfg = load_json(HERE, "configs", "qwen2.5-coder-1.5b.json")
    # one layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536 x 8960
    layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    assert layer == 46_792_704
    s = fused_step.sizes(cfg)
    assert s["layer_matmul_params"] == 28 * layer
    assert s["head_params"] == 151936 * 1536
    assert s["kv_bytes_per_token"] == 28 * 2 * 2 * 128 * 2 == 28672
    # 32 decode rows at 300 tokens of context each
    ops, byts = fused_step.ops_and_bytes(cfg, tokens=32, sampled=32,
                                         contexts=32 * 300)
    assert ops == (2 * 28 * layer * 32 + 2 * 151936 * 1536 * 32
                   + 28 * 4 * 12 * 128 * 9600)
    assert byts == (2 * (28 * layer + 151936 * 1536)
                    + 28672 * (9600 + 32) + 2 * 2 * 1536 * 32)
    peaks = load_json(HERE, "peaks.json")["TPU v5 lite"]
    t = fused_step.least_seconds(cfg, peaks, 32, 32, 9600)
    assert t == byts / 819e9          # bytes bound: weights dominate
    assert 3.9e-3 < t < 4.2e-3


def test_mha_kv_is_seven_times_qwen():
    """deepseek-coder-1.3b's published widths (MHA 16/16 x 128, 24 layers):
    the configuration the next serving cell brings, PERF.md section 7."""
    q = fused_step.sizes(load_json(HERE, "configs",
                                   "qwen2.5-coder-1.5b.json"))
    d = fused_step.sizes({"hidden_size": 2048, "intermediate_size": 5504,
                          "num_attention_heads": 16,
                          "num_key_value_heads": 16,
                          "num_hidden_layers": 24, "vocab_size": 32256})
    assert d["kv_bytes_per_token"] == 196608
    assert round(d["kv_bytes_per_token"] / q["kv_bytes_per_token"], 2) == 6.86
