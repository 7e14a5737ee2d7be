"""HF-layout round trip through the serve path (eval_hf_roundtrip.py).

Round-4 review: the production loading posture — an HF model dir
plus an HF tokenizer dir, cold-loaded and served — executed end to end
(ref ``sendLLMMessage.impl.ts:927``: the reference serves real
checkpoints; zero egress here, so the checkpoint is our own export and
the loading path is identical)."""

import sys

import jax
import pytest

sys.path.insert(0, "/root/repo")

from eval_hf_roundtrip import build_hf_tokenizer_dir, roundtrip


def test_hf_tokenizer_dir_is_real(tmp_path):
    from senweaver_ide_tpu.models.tokenizer import HFTokenizer

    d = build_hf_tokenizer_dir(str(tmp_path / "tok"))
    tok = HFTokenizer(d)
    ids = tok.encode("def main():", add_bos=True)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "def main():"
    # ids must be in-range for the tiny model's 512-entry vocab
    assert all(0 <= i < 512 for i in ids)


def test_roundtrip_exact_parity_tiny(tmp_path):
    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.transformer import init_params

    cfg = get_config("tiny-test")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok_dir = build_hf_tokenizer_dir(str(tmp_path / "tok"))
    leg = roundtrip(cfg, params, tok_dir=tok_dir, label="t",
                    decode_tokens=6)
    assert leg["params_exact_parity"], leg["param_mismatches"]
    assert leg["decode_parity"]
    assert leg["decode_tokens"] == 6
