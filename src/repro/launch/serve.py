"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``:
prefill a batch of prompts and greedy-decode with the jitted one-token step."""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from ..configs import get_config, smoke_config
from ..models.lm import StagedLM
from ..runtime.serve_loop import ServeLoopConfig, run_serving
from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--override", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    ov = json.loads(args.override) if args.override else {}
    cfg = smoke_config(args.arch, **ov) if args.smoke else get_config(args.arch, **ov)
    if cfg.modality != "text":
        print(f"[serve] {cfg.name} is {cfg.modality}; serving the text-token "
              "decoder path requires token inputs — using random tokens for "
              "the backbone" if cfg.modality == "vlm" else
              "[serve] audio backbone: decoding over codec tokens")
    model = StagedLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    if cfg.modality == "vlm":
        # serve the gemma decoder without an image prefix (text-only mode)
        import dataclasses
        cfg = dataclasses.replace(cfg, prefix_len=0, modality="text")
        model = StagedLM(cfg)

    loop = ServeLoopConfig(max_new_tokens=args.max_new_tokens,
                           max_len=args.prompt_len + args.max_new_tokens + 1)
    if cfg.modality == "audio_embed":
        print("[serve] audio arch: skipping (frontend stub has no tokenizer)")
        return 0
    out = run_serving(cfg, params, prompts, loop, model=model)
    print(f"[serve] prefill {out['prefill_s']*1e3:.1f} ms, "
          f"decode {out['decode_tokens_per_s']:.1f} tok/s")
    print("[serve] sample generation:", out["generations"][0][:12].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
