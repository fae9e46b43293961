"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Quantizes the weights with group-wise PTQ — the paper's W8A8 by default,
or any registry format / mixed-precision policy via --quantize-format —
then serves a batch of requests (greedy by default, like the paper's SQuAD
evaluation).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import format_breakdown
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build, load_config
from repro.serving.engine import InferenceEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=64, help="tokens to generate")
    ap.add_argument("--no-quantize", action="store_true",
                    help="fp32 'PS baseline' instead of quantized weights")
    ap.add_argument("--quantize-format", default=None,
                    help="registry format (int8, int4) or policy preset "
                         "(mixed); default: the arch config's quant_format")
    ap.add_argument("--kv-quant", default=None, choices=["int8", "fp8"],
                    help="store the KV cache quantized (per-row scales; "
                         "dequantized in-kernel). Needs a paged-capable "
                         "arch; incompatible with --spec-k")
    ap.add_argument("--sampler", default="greedy", choices=["greedy", "top_p"])
    ap.add_argument("--top-p", type=float, default=0.9,
                    help="nucleus mass for --sampler top_p")
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="softmax temperature for --sampler top_p")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ragged", action="store_true",
                    help="serve a mixed-length trace through serve_ragged "
                         "(paged/continuous-batching scheduler where supported)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots for --ragged continuous batching")
    ap.add_argument("--mode", default="auto",
                    help="--ragged scheduler: auto, paged, continuous, or "
                         "bucketed (auto prefers paged; validated against "
                         "the arch's capabilities, not a static list)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV block size (tokens) for the paged scheduler")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode chunk: verify the current token "
                         "plus spec_k-1 drafted candidates per forward pass "
                         "(0 = off; needs >= 2)")
    ap.add_argument("--drafter", default="ngram",
                    help="speculative drafter: 'ngram' (zero-weight "
                         "prompt-lookup) or 'model:<arch-id>' (small "
                         "registry model, greedy drafts)")
    ap.add_argument("--sanitize", action="store_true",
                    help="repro-san debug mode (DESIGN.md §13): shadow "
                         "block/slot tracking, poison-on-free UAF detection, "
                         "NaN/Inf tripwires (equivalent to REPRO_SAN=1)")
    args = ap.parse_args(argv)
    use_compile_cache()
    sampler_kw = ({"p": args.top_p, "temperature": args.temperature}
                  if args.sampler == "top_p" else None)
    spec_k = args.spec_k or None
    drafter = None
    if spec_k:
        from repro.serving.spec import resolve_drafter

        drafter = resolve_drafter(args.drafter, reduced=args.reduced,
                                  seed=args.seed + 7)

    cfg = load_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    cache_len = args.prompt_len + args.steps + (spec_k or 0)
    if args.ragged:
        from repro.serving.batching import bucket_length

        # ragged prompts are padded up to power-of-two buckets
        cache_len = max(cache_len, bucket_length(args.prompt_len))
    quantize: bool | str = not args.no_quantize
    if quantize and args.quantize_format is not None:
        quantize = args.quantize_format
    if spec_k and args.kv_quant:
        ap.error("--kv-quant is incompatible with --spec-k (the verify pass "
                 "rolls the cache write cursor back; quantized rows cannot "
                 "be partially rewritten)")
    try:
        engine = InferenceEngine(model, params, cache_len=cache_len,
                                 quantize=quantize, kv_quant=args.kv_quant,
                                 sanitize=True if args.sanitize else None)
    except ValueError as e:
        ap.error(str(e))
    breakdown = format_breakdown(engine.params)
    print(f"arch: {cfg.arch_id}  quantized bytes fraction: "
          f"{engine.quantized_fraction:.3f}  "
          + "  ".join(f"{k}: {v / 1e6:.2f}MB" for k, v in sorted(breakdown.items())))

    rng = np.random.default_rng(args.seed)

    if args.ragged:
        from repro.serving.batching import Request, serve_ragged

        lengths = rng.integers(2, args.prompt_len + 1, size=(args.batch,))
        reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=(n,)).tolist())
                for i, n in enumerate(lengths)]
        from repro.serving.batching import resolve_mode

        try:
            mode = resolve_mode(engine, args.mode)    # resolved for the report
        except ValueError as e:
            ap.error(str(e))    # lists the valid modes for this arch
        kw = dict(sampler=args.sampler, sampler_kw=sampler_kw,
                  slots=args.slots, mode=mode, block_size=args.block_size,
                  spec_k=spec_k, drafter=drafter)
        serve_ragged(engine, reqs, args.steps, **kw)     # warm/compile
        t0 = time.perf_counter()
        out = serve_ragged(engine, reqs, args.steps, **kw,
                           key=jax.random.PRNGKey(args.seed + 1))
        hot = time.perf_counter() - t0
        toks = sum(r.tokens.shape[0] for r in out)
        print(f"ragged ({mode}, lengths {sorted(lengths.tolist())}): "
              f"{toks} tokens in {hot:.2f}s ({toks / hot:.2f} tok/s)")
        print("first sequence:", out[0].tokens[:16].tolist())
        return out

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        dtype=jnp.int32)}
    if cfg.model_type == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, args.prompt_len, cfg.d_model)).astype(np.float32))

    t0 = time.perf_counter()
    res = engine.generate(batch, args.steps, sampler=args.sampler,
                          sampler_kw=sampler_kw, spec_k=spec_k,
                          drafter=drafter,
                          key=jax.random.PRNGKey(args.seed))
    jax.block_until_ready(res.tokens)
    warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    res = engine.generate(batch, args.steps, sampler=args.sampler,
                          sampler_kw=sampler_kw, spec_k=spec_k,
                          drafter=drafter,
                          key=jax.random.PRNGKey(args.seed + 1))
    jax.block_until_ready(res.tokens)
    hot = time.perf_counter() - t0

    toks = args.batch * args.steps
    print(f"generated {toks} tokens: warm {warm:.2f}s, hot {hot:.2f}s "
          f"({toks / hot:.2f} tok/s)")
    if res.spec_stats:
        st = res.spec_stats
        acc = st["accepted"] / max(st["drafted"], 1)
        print(f"speculative: {st['verify_steps']} verify steps for "
              f"{st['generated']} tokens "
              f"({st['verify_steps'] / max(st['generated'], 1):.2f} fwd/tok, "
              f"acceptance {acc:.2f})")
    print("first sequence:", np.asarray(res.tokens[0])[:16].tolist())
    return res


if __name__ == "__main__":
    main()
