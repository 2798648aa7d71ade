"""Decode benchmark: GPT-2 KV-cache generation throughput on this chip.

Prints ONE JSON line with the headline metric (tokens/sec at the largest
batch) plus the measured matrix VERDICT r2 item 3 asks for:
- tokens/sec + steady-state decode-step ms per batch size (default 1, 16)
- prefill latency (ms) for the prompt pass
- A/Bs at the largest batch: int8 KV cache on/off, chunked vs unchunked
  prefill — measured, not reasoned.

The reference has no decode subsystem (encoder-only model list,
single-shot batch runtime — SURVEY.md §2.4), so there is no reference
baseline: `vs_baseline` is null and the numbers stand as this
framework's own record (docs/DECODE.md keeps the history).

Method notes: generation runs through the same single-stage
DecodePipeline users get from tools/generate.py (compiled prefill + one
compiled decode-step program; steps dispatch asynchronously, the final
token concat fences). Steady-state step time is measured as
(t(N tokens) - t(N0 tokens)) / (N - N0), which cancels both the prefill
and the fixed dispatch/readback overhead.
Weights are random (zero egress); decode timing is weight-independent
(same matmul shapes, no data-dependent control flow).
"""
import argparse
import json
import time


def _time_once(pipe, ids, new_tokens, **kw):
    import numpy as np
    tik = time.monotonic()
    out = pipe.generate(ids, new_tokens, **kw)
    np.asarray(out)            # fence
    return time.monotonic() - tik


def bench_pipe(pipe, ids, new_tokens, prefill_ubatch=None, reps=5):
    """(tokens/sec, steady step ms, prefill ms) for one pipeline+batch.

    Step time = median over `reps` of INTERLEAVED (t(N) - t(N/2)) pairs,
    divided by the N/2 step difference. Both lengths are step-dominated
    (so prefill + the fixed dispatch overhead cancel in each pair) and
    back-to-back pairing + median kills slow drift and outliers —
    min-of-reps on each length separately composed two different outlier
    floors and once produced a *negative* step time on chip."""
    if new_tokens < 2:
        raise ValueError("steady-state step estimation needs "
                         f"new_tokens >= 2, got {new_tokens}")
    kw = dict(prefill_ubatch=prefill_ubatch)
    n_half = max(1, new_tokens // 2)
    # warm with the FULL token budget so every attend bucket the timed
    # runs will cross is compiled up front
    pipe.generate(ids, new_tokens, **kw)
    deltas, fulls, halves = [], [], []
    for _ in range(reps):
        t_full = _time_once(pipe, ids, new_tokens, **kw)
        t_half = _time_once(pipe, ids, n_half, **kw)
        fulls.append(t_full)
        halves.append(t_half)
        deltas.append(t_full - t_half)
    import statistics
    step_s = statistics.median(deltas) / (new_tokens - n_half)
    batch = ids.shape[0]
    tok_per_sec = batch * new_tokens / min(fulls)
    # prefill latency ~= t_half minus its decode steps
    prefill_ms = max(0.0, (min(halves) - n_half * step_s)) * 1e3
    return tok_per_sec, step_s * 1e3, prefill_ms


def main():
    from pipeedge_tpu.utils import enable_compile_cache

    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("-m", "--model-name", default="gpt2")
    p.add_argument("--prompt-len", default=128, type=int)
    p.add_argument("--new-tokens", default=64, type=int)
    p.add_argument("--max-len", default=1024, type=int,
                   help="KV cache capacity; headroom past prompt+new is "
                        "what bucketed attend saves (serving allocates "
                        "for the longest request, not the current one)")
    p.add_argument("--batches", default="1,16",
                   help="comma-separated batch sizes; the largest carries "
                        "the headline metric and the A/Bs")
    p.add_argument("-t", "--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    args = p.parse_args()
    batches = sorted(int(b) for b in args.batches.split(","))

    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode

    cfg = registry.get_model_config(args.model_name)
    total = registry.get_model_layers(args.model_name)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    max_len = max(args.max_len, args.prompt_len + args.new_tokens)
    if cfg.max_position_embeddings:  # clamp headroom to model capacity
        max_len = min(max_len, cfg.max_position_embeddings)
    decode.validate_capacity(cfg, max_len, args.prompt_len, args.new_tokens)

    _, params, _ = registry.module_shard_factory(
        args.model_name, None, 1, total, dtype=dtype, unroll=False)
    family = registry.get_model_entry(args.model_name).family.FAMILY

    def make_pipe(cache_bits=0, attend_floor=64):
        return decode.DecodePipeline(
            family, cfg, [(1, total)], [params], max_len=max_len,
            dtype=dtype, cache_bits=cache_bits, attend_floor=attend_floor)

    rng = np.random.default_rng(0)
    pipe = make_pipe()
    per_batch = {}
    for b in batches:
        ids = rng.integers(0, cfg.vocab_size, size=(b, args.prompt_len))
        tps, step_ms, prefill_ms = bench_pipe(pipe, ids, args.new_tokens)
        per_batch[b] = {"tokens_per_sec": round(tps, 1),
                        "decode_step_ms": round(step_ms, 3),
                        "prefill_ms": round(prefill_ms, 1)}

    b_big = batches[-1]
    ids_big = rng.integers(0, cfg.vocab_size, size=(b_big, args.prompt_len))

    # A/B: int8 KV cache (same prompt set, fresh pipeline)
    tps_int8, step_int8, _ = bench_pipe(make_pipe(cache_bits=8), ids_big,
                                        args.new_tokens)
    # A/B: chunked prefill (pipelines the prompt pass in batch chunks)
    chunk = max(1, b_big // 4)
    _, _, prefill_chunked = bench_pipe(pipe, ids_big, args.new_tokens,
                                       prefill_ubatch=chunk)
    # A/B: bucketed vs full-window decode-step attention (the default
    # pipe buckets at floor 64; the full pipe always attends max_len)
    tps_full, step_full, _ = bench_pipe(make_pipe(attend_floor=max_len),
                                        ids_big, args.new_tokens)

    # speculative-verify span efficiency: ONE extend() over a
    # (gamma+1)-token span vs gamma+1 serial decode steps — the
    # mechanical upper bound on speculative decoding's per-round win
    # (realized speedup scales with draft acceptance)
    span_k = 5

    def time_span():
        import numpy as _np
        _, caches = pipe._prefill(jnp.asarray(ids_big, jnp.int32))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        size=(b_big, span_k)), jnp.int32)
        out, caches = pipe.extend(toks, caches, args.prompt_len)  # compile
        # warm the fence too: the argmax program would otherwise compile
        # inside the timed window
        _np.asarray(jnp.argmax(out.astype(jnp.float32), -1))
        out, caches = pipe.extend(toks, caches, args.prompt_len)
        _np.asarray(jnp.argmax(out.astype(jnp.float32), -1))
        # chain extends with ONE device-side-argmax fence at the end:
        # dispatch is async, so the fixed dispatch/readback round trip
        # amortizes away and the quotient is device time per span —
        # comparable to decode_step_ms, whose estimator cancels the same
        # overhead.
        reps = 7
        tik = time.monotonic()
        for _ in range(reps):
            out, caches = pipe.extend(toks, caches, args.prompt_len)
        _np.asarray(jnp.argmax(out.astype(jnp.float32), -1))
        return (time.monotonic() - tik) / reps * 1e3

    span_ms = time_span()
    serial_ms = span_k * per_batch[b_big]["decode_step_ms"]

    # prefix caching: monolithic b-row prefill vs shared-prefix reuse
    # (prefix prefilled once at B=1, suffixes run as one span). Single
    # device executes queued dispatches in order, so N un-chained calls
    # + one fence measure N x device time + one RTT, amortized.
    def time_prefix_reuse(suffix_len=8, reps=5):
        import numpy as _np
        from pipeedge_tpu.parallel.decode import _repeat_batch
        suffix_len = min(suffix_len, max(1, args.prompt_len // 2))
        p_len = args.prompt_len - suffix_len   # >= 1 by construction
        ids_full = jnp.asarray(ids_big, jnp.int32)
        fence = lambda x: _np.asarray(
            jnp.argmax(x[:, -1].astype(jnp.float32), -1))
        out, _ = pipe._prefill(ids_full)
        fence(out)                                  # warm monolithic
        tik = time.monotonic()
        for _ in range(reps):
            out, _ = pipe._prefill(ids_full)
        fence(out)
        mono_ms = (time.monotonic() - tik) / reps * 1e3
        handle = pipe.precompute_prefix(ids_full[:1, :p_len])
        out, _ = pipe.extend(  # warm the suffix span + tiled caches
            ids_full[:, p_len:],
            [_repeat_batch(c, b_big) for c in handle["caches"]], p_len)
        fence(out)
        tik = time.monotonic()
        for _ in range(reps):
            caches = [_repeat_batch(c, b_big) for c in handle["caches"]]
            out, _ = pipe.extend(ids_full[:, p_len:], caches, p_len)
        fence(out)
        reuse_ms = (time.monotonic() - tik) / reps * 1e3
        return p_len, suffix_len, mono_ms, reuse_ms

    import jax
    p_len, s_len, mono_ms, reuse_ms = time_prefix_reuse()

    print(json.dumps({
        "metric": f"{args.model_name}_decode_tokens_per_sec_b{b_big}",
        "value": per_batch[b_big]["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,     # the reference has no decode subsystem
        "per_batch": {str(b): v for b, v in per_batch.items()},
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "max_len": max_len,
        "dtype": args.dtype,
        "int8_kv": {"tokens_per_sec": round(tps_int8, 1),
                    "decode_step_ms": round(step_int8, 3)},
        "chunked_prefill_ms": round(prefill_chunked, 1),
        "whole_prefill_ms": per_batch[b_big]["prefill_ms"],
        "prefill_chunk": chunk,
        "full_window_attend": {"tokens_per_sec": round(tps_full, 1),
                               "decode_step_ms": round(step_full, 3)},
        "verify_span": {"k": span_k, "extend_ms": round(span_ms, 3),
                        "serial_ms": round(serial_ms, 3),
                        "speedup_bound": round(serial_ms / span_ms, 2)
                        if span_ms > 0 else None},
        "prefix_reuse": {"prefix_len": p_len, "suffix_len": s_len,
                         "monolithic_prefill_ms": round(mono_ms, 3),
                         "suffix_span_ms": round(reuse_ms, 3),
                         "speedup": round(mono_ms / reuse_ms, 2)
                         if reuse_ms > 0 else None},
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
