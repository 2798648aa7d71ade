"""End-to-end speculative decoding A/B: host-sync vs device-sync rounds.

The claim under measurement (docs/DECODE.md): every host readback has a
fixed cost, so host-sync speculative decoding pays (gamma+1) round trips
per round while `sync='device'` fuses the
whole round — draft catch-up, gamma-1 draft steps, verify span,
acceptance count — into ONE compiled program with ONE packed readback
(parallel/speculative.py). This bench records tokens/sec and measured
syncs/token for plain greedy, host-sync, and device-sync speculative
decoding with identical tokens.

The draft is an EARLY-EXIT self-draft (Draft&Verify-style): the first
`--draft-fraction` of the target's own blocks plus its shared embed/
final head. That makes the draft genuinely ~2x cheaper than the target
AND gives real (measured, not simulated) acceptance even on seeded
random weights — a random-init transformer's residual stream changes
slowly across blocks, so the truncated model's argmax frequently agrees
with the full model's. Acceptance is reported; all speedups are
interleaved same-session A/Bs.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model-name", default="gpt2")
    p.add_argument("-b", "--batch", default=8, type=int)
    p.add_argument("--prompt-len", default=64, type=int)
    p.add_argument("--new-tokens", default=64, type=int)
    p.add_argument("--gammas", default="2,4")
    p.add_argument("--draft-fraction", default=0.5, type=float)
    p.add_argument("--max-len", default=256, type=int)
    p.add_argument("-t", "--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--reps", default=3, type=int)
    args = p.parse_args()

    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode
    from pipeedge_tpu.parallel.speculative import SpeculativeDecoder

    cfg = registry.get_model_config(args.model_name)
    total = registry.get_model_layers(args.model_name)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    max_len = min(max(args.max_len,
                      args.prompt_len + args.new_tokens
                      + max(int(g) for g in args.gammas.split(","))),
                  cfg.max_position_embeddings or 10**9)
    _, params, _ = registry.module_shard_factory(
        args.model_name, None, 1, total, dtype=dtype, unroll=False)
    family = registry.get_model_entry(args.model_name).family.FAMILY
    target = decode.DecodePipeline(family, cfg, [(1, total)], [params],
                                   max_len=max_len, dtype=dtype)

    # early-exit self-draft: first K of the target's own stacked blocks
    # with the shared embed + final head
    n_draft = max(1, int(cfg.num_hidden_layers * args.draft_fraction))
    d_cfg = dataclasses.replace(cfg, num_hidden_layers=n_draft)
    d_params = dict(params)
    d_params["blocks"] = jax.tree_util.tree_map(
        lambda x: x[:n_draft], params["blocks"])
    draft = decode.DecodePipeline(family, d_cfg, [(1, 4 * n_draft)],
                                  [d_params], max_len=max_len, dtype=dtype)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       size=(args.batch, args.prompt_len))
    n = args.new_tokens

    def timed(fn):
        out = fn()                     # warm (compile)
        want = np.asarray(out)         # fence
        best = []
        for _ in range(args.reps):
            tik = time.monotonic()
            got = np.asarray(fn())     # wall time incl. the final fetch
            best.append(time.monotonic() - tik)
            np.testing.assert_array_equal(got, want)
        return want, float(np.median(best))

    plain_out, plain_s = timed(lambda: target.generate(ids, n))
    gammas = {}
    for g_str in args.gammas.split(","):
        g = int(g_str)
        host = SpeculativeDecoder(target, draft, gamma=g, sync="host")
        dev = SpeculativeDecoder(target, draft, gamma=g, sync="device")
        host_out, host_s = timed(lambda: host.generate(ids, n))
        dev_out, dev_s = timed(lambda: dev.generate(ids, n))
        # the round-5 mechanism claim: device and host sync modes are
        # token-identical (same target programs)
        np.testing.assert_array_equal(dev_out, host_out)
        # speculative-vs-plain is bitwise-exact for f32 caches (the
        # tests); at bf16 the K-token verify span's reduction order
        # differs from serial steps, so argmax can flip on near-ties —
        # pervasive on random-init (near-uniform) logits, rare at real
        # logit margins. MEASURED here, not asserted:
        agree = float(np.mean(np.asarray(dev_out) == np.asarray(plain_out)))
        gammas[g] = {
            "plain_token_agreement": round(agree, 4),
            "host": {"tokens_per_sec": round(args.batch * n / host_s, 1),
                     "syncs": host.last_sync_count,
                     "syncs_per_token": round(host.last_sync_count / n, 3)},
            "device": {"tokens_per_sec": round(args.batch * n / dev_s, 1),
                       "syncs": dev.last_sync_count,
                       "syncs_per_token": round(dev.last_sync_count / n, 3)},
            "acceptance": (round(host.last_acceptance_rate, 3)
                           if host.last_acceptance_rate is not None
                           else None),
            "device_vs_host": round(host_s / dev_s, 2),
            "device_vs_plain": round(plain_s / dev_s, 2),
        }

    best_g = max(gammas, key=lambda g: gammas[g]["device"]["tokens_per_sec"])
    print(json.dumps({
        "metric": "speculative_decode_tokens_per_sec",
        "value": gammas[best_g]["device"]["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,    # the reference has no decode subsystem
        "plain_tokens_per_sec": round(args.batch * n / plain_s, 1),
        "gammas": {str(g): v for g, v in gammas.items()},
        "model": args.model_name, "draft_blocks": n_draft,
        "target_blocks": cfg.num_hidden_layers,
        "batch": args.batch, "prompt_len": args.prompt_len,
        "new_tokens": n, "dtype": args.dtype,
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
