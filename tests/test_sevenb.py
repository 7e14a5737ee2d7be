"""6.7B feasibility machinery (eval_sevenb.py / round-3 review).

The full-size run is SEVENB_r04.json; these tests pin the arithmetic
and run the streamed int8 loader + real decode at a shrunken
LLaMA-architecture shape (same code path, minutes not hours)."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from eval_sevenb import sizing_table, streamed_int8_init
from senweaver_ide_tpu.models.config import ModelConfig


def small_llama_config():
    return ModelConfig(
        name="sevenb-slice-test", vocab_size=512, hidden_size=64,
        intermediate_size=160, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, max_seq_len=512, kv_quant=True)


def test_sizing_table_exact_param_count():
    """Sizing must agree with the real init's leaf count."""
    import jax

    from senweaver_ide_tpu.models import init_params

    config = small_llama_config()
    table = sizing_table(config)
    params = init_params(config, jax.random.PRNGKey(0))
    real = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert table["params_total"] == real


def test_sizing_table_sevenb_plans():
    from senweaver_ide_tpu.models import get_config

    table = sizing_table(get_config("deepseek-coder-6.7b"))
    assert 6.6e9 < table["params_total"] < 6.9e9
    # the ladder's claim: full FT cannot fit one chip, QLoRA int8 can,
    # with real decode batch left over
    assert not table["fits_16gb"]["full_ft_bf16"]
    assert table["fits_16gb"]["qlora_int8_base"]
    assert table["decode_slots_at_4k"]["qlora_int8_base_int8kv"] >= 4


def test_streamed_init_matches_quantize_format_and_serves(tmp_path):
    """The layer-streamed int8 tree must be byte-compatible with
    models/quantize.py output and drive the REAL engine decode path."""
    import jax
    import jax.numpy as jnp

    from senweaver_ide_tpu.models.quantize import is_quantized
    from senweaver_ide_tpu.parallel.sharding import param_specs
    from senweaver_ide_tpu.rollout import RolloutEngine

    config = small_llama_config()
    params = streamed_int8_init(config, seed=0)
    assert is_quantized(params)
    assert params["layers"]["wq"].dtype == jnp.int8
    assert params["layers"]["wq_scale"].dtype == jnp.float32
    assert params["lm_head"].dtype == jnp.int8
    param_specs(params)           # raises KeyError on any gap

    engine = RolloutEngine(params, config, num_slots=1, max_len=64,
                           eos_id=None, seed=0)
    rid = engine.submit([1, 2, 3], max_new_tokens=4)
    while not engine.is_done(rid):
        engine.step()
    assert len(engine.result(rid)) == 4
    assert engine.stats()["weight_quant"] == 1
