"""Quickstart: serve a policy with continuous batching.

Serves seeded random weights of any preset on whatever device JAX has —
the tiny test model by default, so it works anywhere; ``--model
qwen2.5-coder-1.5b`` serves the flagship's published shape (on a TPU
chip: run it through the builder's chip tool). Point --model-dir at a
local HF-layout checkpoint (e.g. a downloaded Qwen/Qwen2.5-Coder-1.5B
snapshot) to serve real weights.

    python examples/serve.py [--model NAME] [--model-dir DIR] [--prompt "def main():"]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny-test",
                    help="preset to serve from seeded random weights")
    ap.add_argument("--model-dir", default=None,
                    help="HF-layout qwen2.5-coder-1.5b checkpoint to load "
                         "instead")
    ap.add_argument("--prompt", default="def fibonacci(n):")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend even where JAX has an "
                         "accelerator")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from senweaver_ide_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from senweaver_ide_tpu.models import get_config, init_params
    from senweaver_ide_tpu.models.tokenizer import ByteTokenizer
    from senweaver_ide_tpu.rollout import RolloutEngine, SampleParams

    if args.model_dir:
        from senweaver_ide_tpu.models import load_hf_params, load_tokenizer
        config = get_config("qwen2.5-coder-1.5b")
        params = load_hf_params(args.model_dir, config)
        tok = load_tokenizer(args.model_dir)
    else:
        config = get_config(args.model)
        params = init_params(config, jax.random.PRNGKey(0))
        tok = ByteTokenizer()

    engine = RolloutEngine(params, config, num_slots=4, max_len=2048,
                           sample=SampleParams(temperature=0.8, top_p=0.95),
                           eos_id=tok.eos_id)
    rid = engine.submit(tok.encode(args.prompt, add_bos=True),
                        max_new_tokens=args.max_new_tokens)
    out = engine.run()[rid]
    print(f"[{config.name}] on {jax.devices()[0].platform}: "
          f"{len(out)} tokens:")
    print(tok.decode(out))


if __name__ == "__main__":
    main()
