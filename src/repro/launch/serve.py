"""Production serving driver: parallel prefill + scan-fused batched decode.

    python -m repro.launch.serve --arch yi-9b --policy shiftadd_deploy \
        --reduced --batch 4 --prompt-len 64 --new-tokens 32

The prompt is consumed in one chunked prefill pass (the Q(KᵀV) linear order
makes it O(P)); decode then runs as a single fused lax.scan over the O(1)
linear-attention state (no KV cache under the ShiftAdd policies). Prefill and
decode throughput are reported separately — they are different regimes
(compute-bound vs latency/memory-bound) and regress independently.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, list_archs
from repro.nn.model import LanguageModel
from repro.serve.decode import make_decode_loop, make_prefill
from repro.utils.logging import get_logger

log = get_logger("repro.launch.serve")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--policy", default="dense",
                    choices=["dense", "shiftadd", "shiftadd_deploy", "stage1"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch, policy=args.policy, reduced=args.reduced)
    cfg = cfg.replace(moe_primitives_capacity=2.0)
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    b, p = prompts.shape
    max_len = p + args.new_tokens

    # Phase-split timing: jit'd parallel prefill, then the fused decode scan.
    prefill = jax.jit(make_prefill(model), donate_argnums=(2,))
    loop = jax.jit(make_decode_loop(model, args.temperature),
                   donate_argnums=(2,))
    t0 = time.perf_counter()
    logits_all, cache = prefill(params, prompts, model.init_cache(b, max_len))
    logits0 = jax.block_until_ready(logits_all[:, -1])
    t1 = time.perf_counter()
    if args.temperature > 0.0:
        keys = jax.random.split(jax.random.PRNGKey(2), args.new_tokens)
    else:
        keys = jnp.zeros((args.new_tokens, 2), jnp.uint32)
    toks, _ = loop(params, logits0, cache, keys)
    toks = jax.block_until_ready(toks)
    t2 = time.perf_counter()

    log.info("prefill: %d prompt tokens in %.3fs (%.1f tok/s incl. compile)",
             b * p, t1 - t0, b * p / (t1 - t0))
    log.info("decode: %d tokens in %.3fs (%.1f tok/s incl. compile, "
             "policy=%s)", b * args.new_tokens, t2 - t1,
             b * args.new_tokens / (t2 - t1), args.policy)
    print(jnp.asarray(toks)[:2])


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
