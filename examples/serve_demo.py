"""Serving demo: prefill a batch of prompts, then batched token decode.

The decode loop lives in ``repro.launch.serve.generate`` — this demo is
a thin driver over it (prefill + cache re-homing + EOS-aware decode with
early exit are the library's job, not the example's). Exercises the
inference path the decode_32k / long_500k dry-run shapes lower at
production scale — here with a smoke model on CPU.

Run:  PYTHONPATH=src python examples/serve_demo.py [--arch yi_6b]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.serve import generate
from repro.models.model import Model
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="enable EOS tracking + early exit")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    model = Model(cfg)
    key = jax.random.key(0)
    params = model.init(key)
    B, P = args.batch, args.prompt_len

    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size, dtype=jnp.int32)
    batch = {"tokens": prompts}
    if cfg.arch_type == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), (B, cfg.vision_tokens, 1024)
        )
    if cfg.num_codebooks:
        batch["tokens"] = jax.random.randint(
            key, (B, P, cfg.num_codebooks), 0, cfg.vocab_size, dtype=jnp.int32
        )

    gen, stats = generate(
        model,
        params,
        batch,
        max_new_tokens=args.new_tokens,
        temperature=args.temperature,
        eos_id=args.eos_id,
        key=jax.random.fold_in(key, 2),
    )
    print(
        f"arch={cfg.name}: prefill {B}x{P} tokens in "
        f"{stats['prefill_s']*1e3:.1f} ms (incl. compile)"
    )
    print(
        f"decoded {stats['decode_steps']} steps x {B} streams in "
        f"{stats['decode_s']*1e3:.0f} ms -> {stats['tokens_per_s']:.0f} tok/s "
        "(CPU, incl. compile)"
    )
    print("sample token ids (stream 0):", np.asarray(gen[0]).reshape(-1)[:16].tolist())


if __name__ == "__main__":
    main()
