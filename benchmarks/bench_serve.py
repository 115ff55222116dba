"""Serving-path benchmark: parallel chunked prefill vs token-by-token cache
warmup, and scan-fused decode throughput. Writes BENCH_serve.json so later
PRs have a trajectory for the serving hot path.

    PYTHONPATH=src python benchmarks/bench_serve.py [--prompt-len 512]

The headline number is `prefill_speedup`: how much faster one chunked
full-prompt pass fills the decode cache than P sequential `decode_step`
dispatches (the pre-refactor warmup path). On the CPU `xla` impl the win is
dominated by dispatch-count (P jitted calls → 1) and the O(P) chunked scan;
on TPU the same structure feeds the fused Pallas kernel.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.policy import STAGE1
from repro.kernels import ops
from repro.nn.model import LanguageModel
from repro.serve.decode import make_decode_loop, make_prefill, make_serve_step
from repro.serve.metrics import gate_percentile, latency_summary


def _model(policy, vocab=512):
    cfg = ModelConfig(name="bench-serve", family="dense", policy=policy,
                      n_layers=4, d_model=256, n_heads=4, n_kv_heads=4,
                      d_ff=512, vocab_size=vocab, dtype="float32",
                      scan_layers=True, remat="none")
    model = LanguageModel(cfg)
    return model, model.init(jax.random.PRNGKey(0)), cfg


def bench(prompt_len=512, batch=4, new_tokens=64, iters=3):
    model, params, cfg = _model(STAGE1)
    max_len = prompt_len + new_tokens
    prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                 0, cfg.vocab_size)

    # -- chunked parallel prefill (one fused pass) --------------------------
    # Per-iteration samples summarized by serve.metrics.latency_summary
    # (nearest-rank percentiles, n/method recorded) instead of ad-hoc means:
    # one GC pause or host hiccup used to shift the whole headline number.
    prefill = jax.jit(make_prefill(model))
    logits_all, cache = prefill(params, prompts,
                                model.init_cache(batch, max_len))  # compile
    jax.block_until_ready(logits_all)
    prefill_samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        logits_all, cache = prefill(params, prompts,
                                    model.init_cache(batch, max_len))
        jax.block_until_ready(logits_all)
        prefill_samples.append(time.perf_counter() - t0)
    prefill_lat = latency_summary(prefill_samples)

    # -- token-by-token warmup (the pre-refactor path) ----------------------
    step = jax.jit(make_serve_step(model))
    warm = model.init_cache(batch, max_len)
    lg, warm = step(params, prompts[:, 0], warm)   # compile
    jax.block_until_ready(lg)

    def warmup_loop():
        c = model.init_cache(batch, max_len)
        lg = None
        for t in range(prompt_len):
            lg, c = step(params, prompts[:, t], c)
        jax.block_until_ready(lg)

    t0 = time.perf_counter()
    warmup_loop()
    warmup_s = time.perf_counter() - t0

    # -- scan-fused decode --------------------------------------------------
    loop = jax.jit(make_decode_loop(model, 0.0))
    keys = jnp.zeros((new_tokens, 2), jnp.uint32)
    logits0 = logits_all[:, -1]
    toks, _ = loop(params, logits0, cache, keys)   # compile
    jax.block_until_ready(toks)
    decode_samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        toks, _ = loop(params, logits0, cache, keys)
        jax.block_until_ready(toks)
        decode_samples.append(time.perf_counter() - t0)
    decode_lat = latency_summary(decode_samples)

    # Stats are read at the percentile the sample count supports (p50 at
    # the CI iteration counts); the scalar *_s keys stay, now defined as
    # that gated percentile rather than a mean.
    gate_key = gate_percentile(iters)
    prefill_s = prefill_lat[gate_key]
    decode_s = decode_lat[gate_key]

    return {
        "impl": ops.default_impl(),
        "backend": jax.default_backend(),
        "arch": "bench-serve(4L,256d,stage1)",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "gate_key": gate_key,
        "prefill_s": prefill_s,
        "prefill_latency": prefill_lat,
        "prefill_toks_per_s": batch * prompt_len / prefill_s,
        "token_by_token_warmup_s": warmup_s,
        "token_by_token_toks_per_s": batch * prompt_len / warmup_s,
        "prefill_speedup": warmup_s / prefill_s,
        "decode_s": decode_s,
        "decode_latency": decode_lat,
        "decode_toks_per_s": batch * new_tokens / decode_s,
    }


def main(rows=None):
    if rows is not None:
        # benchmarks/run.py harness mode: small prompt, CSV row contract.
        rec = bench(prompt_len=64, batch=2, new_tokens=8, iters=1)
        rows.append(("serve_prefill", rec["prefill_s"] * 1e6,
                     f"speedup_vs_warmup={rec['prefill_speedup']:.1f}"))
        rows.append(("serve_decode", rec["decode_s"] * 1e6,
                     f"toks_s={rec['decode_toks_per_s']:.0f}"))
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()

    rec = bench(args.prompt_len, args.batch, args.new_tokens)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"prefill   : {rec['prefill_toks_per_s']:>10.0f} tok/s "
          f"({rec['prefill_s'] * 1e3:.1f} ms for {args.batch}x{args.prompt_len})")
    print(f"warmup    : {rec['token_by_token_toks_per_s']:>10.0f} tok/s "
          f"(token-by-token, {rec['token_by_token_warmup_s'] * 1e3:.1f} ms)")
    print(f"speedup   : {rec['prefill_speedup']:>10.1f}x (chunked prefill vs warmup)")
    print(f"decode    : {rec['decode_toks_per_s']:>10.0f} tok/s (scan-fused)")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
