"""HF-layout checkpoint + tokenizer dir → cold load → engine decode parity.

Round-4 review: ``models/load.py`` and ``HFTokenizer`` existed
but no artifact drove the PRODUCTION loading posture end to end — an
HF-layout model dir plus an HF tokenizer dir, cold-loaded, served by
the engine (the reference serves real checkpoints,
``sendLLMMessage.impl.ts:927``; this environment has zero egress, so
the checkpoint is generated OFFLINE by our own export — the loading
code path is identical to loading a downloaded one).

Round trip, twice:
  1. **trained tiny policy** (the capacity/uplift checkpoint when
     present, else a fresh short pretrain): train state →
     ``export_hf_params`` → safetensors dir → ``load_hf_params`` →
     leaf-exact parity → RolloutEngine greedy decode parity
     (source-params engine vs loaded-params engine, same ids).
  2. **real config at shape** (``qwen2.5-coder-0.5b``): random-init →
     same export/load/decode-parity path, proving the real layout
     (GQA dims, qkv biases, untied head) survives the round trip.

The HF tokenizer dir is built offline with the ``tokenizers`` library
(char-level WordLevel vocab saved via ``PreTrainedTokenizerFast``) and
loaded through our ``HFTokenizer`` wrapper → AutoTokenizer — a real
tokenizer directory, not a monkeypatch.

    python eval_hf_roundtrip.py

Prints ONE JSON line (the HF_ROUNDTRIP_r05 artifact).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def build_hf_tokenizer_dir(out_dir: str) -> str:
    """A genuine HF tokenizer directory, created offline: char-level
    WordLevel vocab (printable ascii + specials) behind
    PreTrainedTokenizerFast.save_pretrained."""
    from tokenizers import Regex, Tokenizer, decoders, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    specials = ["<unk>", "<s>", "</s>", "<pad>"]
    vocab = {s: i for i, s in enumerate(specials)}
    for i in range(32, 127):
        vocab[chr(i)] = len(vocab)
    tk = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
    tk.pre_tokenizer = pre_tokenizers.Split(Regex("."), "isolated")
    tk.decoder = decoders.Fuse()     # char vocab: concatenate, no spaces
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tk, unk_token="<unk>", bos_token="<s>",
        eos_token="</s>", pad_token="<pad>")
    fast.save_pretrained(out_dir)
    return out_dir


def greedy_ids(engine, prompt_ids, n: int):
    rid = engine.submit(list(prompt_ids), max_new_tokens=n)
    engine.run()
    return engine.result(rid)


def roundtrip(config, params, *, tok_dir: str, label: str,
              decode_tokens: int = 12, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from senweaver_ide_tpu.models.load import (available_hf_keys,
                                               export_hf_params,
                                               load_hf_params)
    from senweaver_ide_tpu.models.tokenizer import HFTokenizer
    from senweaver_ide_tpu.rollout import RolloutEngine
    from senweaver_ide_tpu.rollout.sampler import SampleParams

    t0 = time.monotonic()
    model_dir = tempfile.mkdtemp(prefix=f"hf_rt_{label}_")
    path = export_hf_params(params, config, model_dir)
    export_wall = time.monotonic() - t0

    # Cold load: fresh arrays from the safetensors file on disk.
    t0 = time.monotonic()
    loaded = load_hf_params(model_dir, config)
    load_wall = time.monotonic() - t0

    src_leaves = jax.tree_util.tree_leaves_with_path(params)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    mismatches = []
    for key, a in src_leaves:
        b = got.get(key)
        if b is None:
            mismatches.append(f"missing {jax.tree_util.keystr(key)}")
        elif not np.array_equal(np.asarray(a, np.float32),
                                np.asarray(b, np.float32)):
            mismatches.append(jax.tree_util.keystr(key))
    exact = not mismatches

    # Serve both trees greedily on the SAME token ids (from the real HF
    # tokenizer dir) — bit-identical samples prove the loaded tree is
    # the served product, not merely numerically close.
    tok = HFTokenizer(tok_dir)
    prompt = tok.encode("def main():", add_bos=True)
    greedy = SampleParams(temperature=0.0)
    eng_src = RolloutEngine(params, config, num_slots=1, max_len=128,
                            sample=greedy, eos_id=None, seed=seed)
    out_src = greedy_ids(eng_src, prompt, decode_tokens)
    del eng_src
    eng_new = RolloutEngine(loaded, config, num_slots=1, max_len=128,
                            sample=greedy, eos_id=None, seed=seed)
    out_new = greedy_ids(eng_new, prompt, decode_tokens)
    del eng_new

    return {
        "label": label,
        "config": config.name,
        "safetensors": os.path.basename(path),
        "hf_keys": len(available_hf_keys(model_dir)),
        "export_wall_s": round(export_wall, 2),
        "cold_load_wall_s": round(load_wall, 2),
        "params_exact_parity": exact,
        "param_mismatches": mismatches[:5],
        "tokenizer": {"dir_files": sorted(os.listdir(tok_dir)),
                      "vocab_size": tok.vocab_size,
                      "prompt_ids": list(prompt)},
        "decode_tokens": decode_tokens,
        "decode_parity": bool(list(out_src) == list(out_new)),
        "decoded_text": tok.decode(out_new),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default="/tmp/cap_tiny_ckpt",
                    help="trained tiny checkpoint (missing → fresh "
                         "short pretrain)")
    ap.add_argument("--real-config", default="qwen2.5-coder-0.5b")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from senweaver_ide_tpu.models import get_config
    from senweaver_ide_tpu.models.transformer import init_params

    t_all = time.monotonic()
    tok_dir = build_hf_tokenizer_dir(tempfile.mkdtemp(prefix="hf_tok_"))

    # Leg 1: TRAINED tiny weights.
    tiny_cfg = get_config("tiny-test")
    if os.path.isdir(args.ckpt):
        from eval_uplift_real import load_policy
        state, _engine, _tok, _cfg = load_policy(args.ckpt,
                                                 seed=args.seed)
        tiny_params, tiny_src = state.params, args.ckpt
    else:
        from eval_uplift_real import pretrain_rule_policy
        state, _eng, _tok, _cfg, _curve = pretrain_rule_policy(
            rounds=12, seed=args.seed, group_size=8)
        tiny_params, tiny_src = state.params, "fresh 12-round pretrain"
    leg1 = roundtrip(tiny_cfg, tiny_params, tok_dir=tok_dir,
                     label="tiny-trained", seed=args.seed)
    leg1["weights_source"] = tiny_src
    print(f"[hf] leg1 {json.dumps(leg1)}", file=sys.stderr, flush=True)

    # Leg 2: REAL config at shape.
    real_cfg = get_config(args.real_config)
    real_params = init_params(real_cfg, jax.random.PRNGKey(args.seed + 1))
    leg2 = roundtrip(real_cfg, real_params, tok_dir=tok_dir,
                     label="real-config", decode_tokens=6, seed=args.seed)
    print(f"[hf] leg2 {json.dumps(leg2)}", file=sys.stderr, flush=True)

    report = {
        "metric": "hf_roundtrip_serve_path",
        "legs": [leg1, leg2],
        "ok": bool(leg1["params_exact_parity"] and leg1["decode_parity"]
                   and leg2["params_exact_parity"]
                   and leg2["decode_parity"]),
        "posture": "export_hf_params → safetensors dir; offline-built "
                   "HF tokenizer dir → AutoTokenizer via HFTokenizer; "
                   "cold load_hf_params → RolloutEngine greedy decode, "
                   "bit-identical to the source params",
        "total_wall_s": round(time.monotonic() - t_all, 1),
    }
    print(json.dumps(report))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:   # always leave a JSON line for the driver
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
