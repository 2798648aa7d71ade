"""Pipelined KV-cache text generation demo (GPT-2 family).

Greedy-decodes synthetic (or file-provided) token prompts through a
block-aligned pipeline partition, printing tokens/sec. Weights load from the
registry's npz (random fallback under zero egress) — the decoding path is
weight-agnostic; pair with `save_model_weights.py` for real checkpoints.

Example:
    python tools/generate.py -m gpt2 -pt 1,24,25,48 -b 8 --new-tokens 32
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from pipeedge_tpu import telemetry  # noqa: E402
from pipeedge_tpu.telemetry import generate_account  # noqa: E402


def prompt_ids(args, cfg):
    """Synthetic prompt token ids [B, prompt_len] (seeded, rank-consistent)."""
    return np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(args.batch_size, args.prompt_len))


def print_summary(args, dt, result, label, accounts=()):
    """`accounts`: the pipeline's `batch_accounts` where the timed batch
    was a call of `DecodePipeline.generate`; its account is the last."""
    print(telemetry.startup_line())
    if accounts:
        print(generate_account.account_line(accounts[-1]))
    print(f"generated {args.batch_size}x{args.new_tokens} tokens in "
          f"{dt:.3f}s = {args.batch_size * args.new_tokens / dt:.1f} tok/s "
          f"({label})")
    print("sample continuation ids:", result[0, args.prompt_len:].tolist())


def run_dcn(args, cfg, total, partition, max_len, dtype):
    """Pipelined decoding across OS processes over TCP (DCN): stage i runs
    on rank i; every rank launches the same command with its own --rank, so
    the step count is known fleet-wide and no control plane is needed. Per
    step, the token's hidden state hops rank-to-rank on CHANNEL_DATA and
    the last rank returns the next-token logits to rank 0 on
    CHANNEL_RESULTS (the same edge discipline as runtime.py's DCN driver).

    Adaptive edge quantization (env ADAPTIVE_QUANT=HEURISTIC|HEURISTIC2|
    CONTROLLER + SEND_CONSTRAINT, reference runtime.py:121-216): each
    non-last rank adapts its OWN output edge's bitwidth on its own measured
    'send' telemetry window, exactly like the runtime driver's DCN mode —
    `--edge-bits` is then the starting bitwidth, and the consumer needs no
    coordination because the bitwidth rides the wire header (comm/wire.py).
    """
    import jax
    import jax.numpy as jnp

    from pipeedge_tpu.comm import dcn, wire
    from pipeedge_tpu.models import registry, stage_cache
    from pipeedge_tpu.parallel import decode

    world = len(partition)
    rank = args.rank
    if not 0 <= rank < world:
        raise SystemExit(f"--rank {rank} outside the {world}-stage partition")
    decode.validate_partition(partition, total)
    decode.validate_capacity(cfg, max_len, args.prompt_len, args.new_tokens)
    addrs = dcn.parse_rank_addrs(args.dcn_addrs, world, 29600)
    l, r = partition[rank]
    _, params, sc = registry.module_shard_factory(
        args.model_name, args.model_file, l, r, stage=rank, dtype=dtype,
        unroll=False)
    family = registry.get_model_entry(args.model_name).family.FAMILY
    prefill_fn, decode_fn = decode.make_stage_fns(family, cfg, sc)
    params = dict(params)
    params["blocks"] = decode.stage_blocks(params)
    pick = decode.make_token_picker(args.temperature, args.top_k)
    prompt = args.prompt_len
    ids = prompt_ids(args, cfg)

    # mutable output-edge bitwidth + adaptive policy (non-last ranks own
    # exactly one edge; the runtime driver's _EdgeQuantState/-callback are
    # reused so policy behavior is identical across both DCN applications)
    edge = adaptive = None
    monitoring_mod = None
    if world > 1 and not sc.is_last:
        import runtime as runtime_mod
        edge = runtime_mod._EdgeQuantState(args.edge_bits)
        if os.getenv(runtime_mod.ENV_ADAPTIVE_QUANT):
            import logging

            import monitoring as monitoring_mod
            logging.basicConfig(level=logging.INFO)
            window = runtime_mod.get_window_size()
            monitoring_mod.init(runtime_mod.MONITORING_KEY_SEND, window,
                                work_type="Mbits")
            monitoring_mod.add_key(runtime_mod.MONITORING_KEY_RECV,
                                   work_type="Mbits")
            adaptive = runtime_mod._make_adaptive_callback([edge], window)
    step_beat = [0]

    with dcn.DistDcnContext(world, rank, addrs) as ctx:
        if adaptive is not None:
            import runtime as runtime_mod
            runtime_mod._register_dcn_monitor_hooks(ctx)

        def run_once(new_tokens):
            """One full fleet-lockstep generation (prefill + steps). Every
            rank executes the same step count, so no control plane is
            needed; returns rank 0's tokens."""
            cache = stage_cache.init_cache(cfg, (r - l + 1) // 4,
                                           args.batch_size, max_len, dtype)
            rng = jax.random.PRNGKey(args.seed)
            tokens = []

            def stage_step(data, pos, fn):
                nonlocal cache
                if not sc.is_first:
                    data = wire.wire_decode(ctx.recv_tensors(rank - 1),
                                            dtype)
                # bucketed attend window: pos is fleet-lockstep, so every
                # rank independently picks the same static bucket
                out, cache = fn(params, data, cache) if pos is None else \
                    fn(params, data, cache, pos,
                       read_len=decode.attend_bucket(pos + 1, max_len,
                                                     args.attend_floor))
                if not sc.is_last:
                    ctx.send_tensors(rank + 1, wire.wire_encode(
                        out, edge.quant_bit if edge is not None else 0))
                    if adaptive is not None:
                        adaptive(step_beat[0], out)
                        step_beat[0] += 1
                elif world > 1:
                    # last position's logits back to rank 0
                    last = out[:, -1] if pos is None else out[:, 0]
                    ctx.send_tensors(0, [np.asarray(last)],
                                     channel=dcn.CHANNEL_RESULTS)
                return out

            def next_token(out, pos):
                nonlocal rng
                if world > 1:
                    logits = jnp.asarray(
                        ctx.recv_tensors(world - 1,
                                         channel=dcn.CHANNEL_RESULTS)[0])
                else:
                    logits = out[:, prompt - 1] if pos is None else out[:, 0]
                rng, sub = jax.random.split(rng)
                return pick(logits.astype(jnp.float32), sub)

            out = stage_step(
                jnp.asarray(ids, jnp.int32) if sc.is_first else None,
                None, prefill_fn)
            if rank == 0:
                tokens.append(next_token(out, None))
            for step in range(1, new_tokens):
                pos = prompt + step - 1
                data = tokens[-1][:, None] if sc.is_first else None
                out = stage_step(data, pos, decode_fn)
                if rank == 0:
                    tokens.append(next_token(out, pos))
            return tokens

        # compile programs fleet-wide with the FULL token budget, so every
        # attend bucket the timed run crosses is already built (a 2-token
        # warmup would leave bucket compiles inside the timed region)
        run_once(args.new_tokens)
        tik = time.monotonic()
        tokens = run_once(args.new_tokens)
        if rank == 0:
            dt = time.monotonic() - tik
            result = np.concatenate(
                [ids, np.stack([np.asarray(t) for t in tokens], axis=1)],
                axis=1)
            print_summary(args, dt, result, f"{world} DCN ranks")
    if monitoring_mod is not None:
        monitoring_mod.finish()


def run_spmd_wave(args, cfg, partition, stage_params, max_len, dtype):
    """`--spmd-wave`: the whole continuous-batching wave schedule compiled
    into shard_map programs over a ('stage',) mesh (n_stages request
    slots, ppermute edges, zero host round-trips per tick)."""
    import jax
    from jax.sharding import Mesh

    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline

    n_stages = len(partition)
    if len(jax.devices()) < n_stages:
        raise SystemExit(f"--spmd-wave needs {n_stages} devices (one per "
                         f"stage), only {len(jax.devices())} visible")
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("stage",))
    wave = SpmdDecodePipeline(registry.get_model_entry(
        args.model_name).family.FAMILY, cfg, partition, stage_params,
        mesh, max_len=max_len, dtype=dtype, edge_bits=args.edge_bits)
    # same prompt convention as solo/--concurrent runs (one prompt_ids()
    # prompt per request slot, per-slot sampling seeds seed+r), so wave
    # throughput and continuations are comparable across demo modes
    wave_ids = np.stack([prompt_ids(args, cfg)] * n_stages)
    kw = dict(temperature=args.temperature, top_k=args.top_k,
              seeds=[args.seed + r for r in range(n_stages)])
    # warm with the SAME token budget: new_tokens sizes the compiled
    # wave programs, so a shorter warmup would compile the wrong ones
    np.asarray(wave.generate(wave_ids, args.new_tokens, **kw))
    tik = time.monotonic()
    out = np.asarray(wave.generate(wave_ids, args.new_tokens, **kw))
    dt = time.monotonic() - tik
    n_tok = n_stages * args.batch_size * args.new_tokens
    print(f"generated {n_stages}x{args.batch_size}x{args.new_tokens} "
          f"tokens in {dt:.3f}s = {n_tok / dt:.1f} tok/s "
          f"({n_stages} stages, SPMD wave decode)")
    print("sample continuation ids:",
          out[0, 0, args.prompt_len:].tolist())


def main():
    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax.numpy as jnp
    from pipeedge_tpu.models import registry
    from pipeedge_tpu.parallel import decode

    parser = argparse.ArgumentParser(
        description="Pipelined KV-cache greedy generation",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-m", "--model-name", default="gpt2",
                        type=registry.decoder_model,
                        help="a registered decoder, or <name>@<blocks>: "
                             "its first blocks with its head")
    parser.add_argument("-M", "--model-file", default=None)
    parser.add_argument("-pt", "--partition", default=None,
                        help="comma-separated layer ranges, e.g. 1,24,25,48 "
                             "(default: single stage)")
    parser.add_argument("-b", "--batch-size", default=4, type=int)
    parser.add_argument("--prompt-len", default=16, type=int)
    parser.add_argument("--new-tokens", default=32, type=int)
    parser.add_argument("--max-len", default=None, type=int,
                        help="cache capacity (default: prompt+new tokens)")
    parser.add_argument("-t", "--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--kv-bits", default=0, type=int, choices=[0, 8],
                        help="int8-quantize the KV cache (halves decode "
                             "HBM traffic; 0 = full precision)")
    parser.add_argument("--attend-floor", default=64, type=int,
                        help="smallest bucketed attend window: decode "
                             "steps attend over the least window of a "
                             "ladder >= the live cache length instead of "
                             "max_len (one compiled variant per bucket; "
                             "four widths a power of two for a plain "
                             "generation, the powers of two elsewhere)")
    parser.add_argument("--tp", default=1, type=int,
                        help="Megatron tensor-parallel degree per stage "
                             "(head-sharded KV cache, shard_map)")
    parser.add_argument("--sp", default=1, type=int,
                        help="sequence-parallel PREFILL degree (causal ring "
                             "attention over the prompt; decode steps stay "
                             "single-device)")
    parser.add_argument("--ep", default=1, type=int,
                        help="expert-parallel degree for MoE models "
                             "(experts shard over an 'ep' mesh per stage); "
                             "combine with --tp for the tp x ep serving "
                             "mesh (attention tp-sharded, experts "
                             "ep-sharded)")
    parser.add_argument("--draft-model", default=None,
                        help="speculative decoding: this (smaller, same-"
                             "vocabulary) model proposes --gamma tokens "
                             "per round, one target span forward verifies "
                             "them; output is token-identical to plain "
                             "greedy decoding of the main model")
    parser.add_argument("--gamma", default=4, type=int,
                        help="draft lookahead per speculative round")
    parser.add_argument("--temperature", default=0.0, type=float,
                        help="sampling temperature (0 = greedy)")
    parser.add_argument("--top-k", default=0, type=int,
                        help="sample only from the k most likely tokens "
                             "(0 = full distribution)")
    parser.add_argument("--seed", default=0, type=int,
                        help="sampling PRNG seed")
    parser.add_argument("--beams", default=0, type=int,
                        help="beam-search width (0 = greedy/sampling; "
                             "local pipeline mode only)")
    parser.add_argument("--prefill-ubatch", default=None, type=int,
                        help="pipeline the prompt pass across stages in "
                             "batch chunks of this size")
    parser.add_argument("--shared-prefix", default=0, type=int,
                        help="prompt caching: treat the first N prompt "
                             "tokens as a prefix shared by every batch "
                             "row — prefilled ONCE (precompute_prefix) "
                             "and reused; the per-row suffixes run as "
                             "one span at the prefix offset")
    parser.add_argument("--concurrent", default=0, type=int,
                        help="continuous batching: decode this many "
                             "concurrent requests (each of -b sequences) "
                             "wave-scheduled across the pipeline stages; "
                             "tokens match solo runs per request")
    parser.add_argument("--spmd-wave", action="store_true",
                        help="compile the whole wave schedule into one "
                             "shard_map program per phase (n_stages "
                             "request slots over a ('stage',) mesh, "
                             "ppermute edges, zero host round-trips per "
                             "tick); greedy or --temperature sampling")
    parser.add_argument("--monitor", action="store_true",
                        help="record per-step heartbeats to decode.csv "
                             "(overwrites an existing decode.csv in cwd)")
    parser.add_argument("--rank", default=0, type=int,
                        help="this process's rank in a DCN fleet")
    parser.add_argument("-sm", "--sched-models-file", default=None)
    parser.add_argument("-sdt", "--sched-dev-types-file", default=None)
    parser.add_argument("-sd", "--sched-dev-file", default=None)
    parser.add_argument("--edge-bits", default=0, type=int,
                        choices=[0, 2, 4, 6, 8, 16],
                        help="quantize stage edges (QuantPipe activation "
                             "compression): DCN wire frames with "
                             "--dcn-addrs, or the [B, S, D] prefill "
                             "ppermute hops with --spmd-wave")
    parser.add_argument("--dcn-addrs", default=None, type=str,
                        help="comma-separated host:port per rank: run the "
                             "pipeline across OS processes over TCP (stage "
                             "i on rank i; launch the same command on every "
                             "rank with its own --rank)")
    args = parser.parse_args()
    if args.new_tokens < 1:
        parser.error("--new-tokens must be >= 1")

    cfg = registry.get_model_config(args.model_name)
    total = registry.get_model_layers(args.model_name)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.partition:
        nums = [int(x) for x in args.partition.split(",")]
        if len(nums) % 2:
            parser.error(f"-pt needs an even count of layer bounds: {nums}")
        partition = list(zip(nums[::2], nums[1::2]))
    elif args.sched_models_file:
        # profile-driven partitioning: the native DP scheduler cuts at
        # sublayer granularity (its cost model is per quarter-block);
        # decoding needs block-aligned stages, so round the cuts to the
        # nearest block boundary
        from pipeedge_tpu.sched.scheduler import sched_pipeline
        # dtype must match the profile records' (dtype, batch_size) key
        # (native/sched_pipeline_main.cpp:135) — chip profiles are bfloat16
        sched = sched_pipeline(args.model_name, 2, 2, args.batch_size,
                               dtype=args.dtype,
                               models_file=args.sched_models_file,
                               dev_types_file=args.sched_dev_types_file,
                               dev_file=args.sched_dev_file)
        if not sched:
            raise SystemExit("No viable schedule found")
        raw = [tuple(int(v) for v in layers)
               for stage in sched for layers in stage.values()]
        partition = decode.round_partition_to_blocks(raw, total)
        if partition != raw:
            print(f"scheduler partition {raw} rounded to block-aligned "
                  f"{partition}")
    else:
        partition = [(1, total)]
    max_len = args.max_len or args.prompt_len + args.new_tokens
    if args.draft_model and args.max_len is None:
        max_len += args.gamma   # verify spans write past the last token
    if args.beams and args.temperature > 0:
        parser.error("--beams and --temperature are mutually exclusive")
    if args.beams and args.monitor:
        parser.error("--monitor records per-step heartbeats only for "
                     "greedy/sampled generation, not --beams")
    if args.beams and args.prefill_ubatch:
        parser.error("--prefill-ubatch applies to greedy/sampled "
                     "generation, not --beams")
    if args.edge_bits and args.dcn_addrs is None and not args.spmd_wave:
        parser.error("--edge-bits applies to DCN stage edges or the SPMD "
                     "wave prefill hops; pass --dcn-addrs or --spmd-wave")
    if args.shared_prefix and (
            args.beams or args.spmd_wave
            or args.prefill_ubatch or args.dcn_addrs is not None):
        # checked BEFORE mode dispatch: every one of these modes branches
        # away earlier than the prefix path, which would otherwise
        # silently ignore --shared-prefix (--draft-model and --concurrent
        # compose: the speculative decoder and the batcher both take
        # prefix handles)
        parser.error("--shared-prefix composes with plain, speculative, "
                     "or --concurrent greedy/sampled generation only "
                     "(not --beams/--spmd-wave/--prefill-ubatch/"
                     "--dcn-addrs)")
    if args.shared_prefix and args.sp > 1 and args.shared_prefix % args.sp:
        parser.error(f"--shared-prefix {args.shared_prefix} must divide "
                     f"by --sp {args.sp} (the prefix is what the sp "
                     "prefill runs on)")
    if args.spmd_wave and (
            args.concurrent or args.beams or args.monitor
            or args.prefill_ubatch
            or args.tp > 1 or args.sp > 1 or args.ep > 1 or args.kv_bits
            or args.dcn_addrs is not None):
        parser.error("--spmd-wave does not compose with --concurrent/"
                     "--beams/--monitor/--prefill-ubatch/--tp/--sp/--ep/"
                     "--kv-bits/--dcn-addrs")
    if args.dcn_addrs is not None:
        if args.tp > 1 or args.sp > 1 or args.ep > 1 or args.kv_bits \
                or args.monitor or args.beams or args.prefill_ubatch:
            parser.error("--dcn-addrs does not compose with --tp/--sp/--ep/"
                         "--kv-bits/--monitor/--beams/--prefill-ubatch in "
                         "this demo")
        run_dcn(args, cfg, total, partition, max_len, dtype)
        return
    stage_params = []
    for i, (l, r) in enumerate(partition):
        _, params, _ = registry.module_shard_factory(
            args.model_name, args.model_file, l, r, stage=i, dtype=dtype,
            unroll=False)  # DecodePipeline wants the stacked block layout
        stage_params.append(params)
    if args.spmd_wave:
        run_spmd_wave(args, cfg, partition, stage_params, max_len, dtype)
        return
    mesh = sp_mesh = ep_mesh = tp_ep_mesh = None
    if args.tp > 1 or args.sp > 1 or args.ep > 1:
        import jax
        from jax.sharding import Mesh
        tp_with_ep = args.tp > 1 and args.ep > 1    # MoE serving: tp x ep
        need = args.tp * args.ep if tp_with_ep else max(args.tp, args.sp,
                                                        args.ep)
        if len(jax.devices()) < need:
            parser.error(f"--tp/--sp/--ep {need} needs {need} devices, "
                         f"only {len(jax.devices())} visible")
        if args.sp > 1 and (args.tp > 1 or args.ep > 1):
            parser.error("--sp is mutually exclusive with --tp/--ep in "
                         "this demo")
        if args.sp > 1 and args.prompt_len % args.sp:
            parser.error(f"--prompt-len {args.prompt_len} must divide by "
                         f"--sp {args.sp}")
        if tp_with_ep:
            tp_ep_mesh = Mesh(np.array(jax.devices()[:need]).reshape(
                args.tp, args.ep), ("tp", "ep"))
        elif args.tp > 1:
            mesh = Mesh(np.array(jax.devices()[:args.tp]), ("tp",))
        elif args.sp > 1:
            sp_mesh = Mesh(np.array(jax.devices()[:args.sp]), ("sp",))
        else:
            ep_mesh = Mesh(np.array(jax.devices()[:args.ep]), ("ep",))
    # shared construction path with tools/serve.py (model lookup /
    # capacity clamp live in one place); params pre-loaded above because
    # the spmd-wave branch needs them directly
    pipe = decode.build_decode_pipeline(
        args.model_name, partition, max_len=max_len, dtype=dtype,
        cache_bits=args.kv_bits, attend_floor=args.attend_floor,
        stage_params=stage_params, mesh=mesh, sp_mesh=sp_mesh,
        ep_mesh=ep_mesh, tp_ep_mesh=tp_ep_mesh)

    heartbeat = None
    if args.monitor:
        import jax
        import monitoring
        monitoring.init("decode", window_size=16, work_type="tokens")

        def heartbeat(step, tokens):
            # per-step heartbeat -> decode.csv. JAX dispatch is async, so
            # fence on the step's tokens to time real emission, not host
            # dispatch. The first beat establishes the time base
            # (runtime.py's safe=False pattern), so decode.csv carries
            # new_tokens - 1 intervals.
            jax.block_until_ready(tokens)
            monitoring.iteration("decode", work=int(tokens.shape[0]),
                                 safe=False)

    ids = prompt_ids(args, cfg)
    p_len = args.shared_prefix
    if p_len:
        # ONE prefix setup for both the plain and speculative modes:
        # validate, make every batch row share the prefix, and prepend
        # it back onto generate()'s prefix-omitting output
        if not 0 < p_len < args.prompt_len:
            parser.error(f"--shared-prefix must be in (0, "
                         f"{args.prompt_len})")
        ids[:, :p_len] = ids[0, :p_len]
        with_prefix = lambda out: np.concatenate([ids[:, :p_len], out],
                                                 axis=1)
    if args.draft_model:
        if (args.temperature > 0 or args.top_k or args.beams
                or args.concurrent or args.monitor or args.spmd_wave
                or args.prefill_ubatch or args.dcn_addrs is not None
                or args.kv_bits):
            parser.error("--draft-model is greedy-exact speculative "
                         "decoding; it does not compose with sampling/"
                         "--beams/--concurrent/--monitor/--spmd-wave/"
                         "--prefill-ubatch/--dcn-addrs, nor --kv-bits "
                         "(int8 span verification is not bit-identical "
                         "to serial int8 steps)")
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        d_total = registry.get_model_layers(args.draft_model)
        _, d_params, _ = registry.module_shard_factory(
            args.draft_model, None, 1, d_total, dtype=dtype, unroll=False)
        d_pipe = decode.DecodePipeline(
            registry.get_model_entry(args.draft_model).family.FAMILY,
            registry.get_model_config(args.draft_model), [(1, d_total)],
            [d_params], max_len=max_len, dtype=dtype,
            attend_floor=args.attend_floor)
        spec = SpeculativeDecoder(pipe, d_pipe, gamma=args.gamma)
        label = (f"{len(partition)} stages, speculative gamma="
                 f"{args.gamma} draft={args.draft_model}")
        if p_len:
            handle = spec.precompute_prefix(ids[:1, :p_len])
            gen = lambda n: with_prefix(np.asarray(spec.generate(
                ids[:, p_len:], n, prefix=handle)))
            label += f", shared prefix {p_len}"
        else:
            gen = lambda n: np.asarray(spec.generate(ids, n))
        gen(min(2, args.new_tokens))          # compile programs
        tik = time.monotonic()
        out = gen(args.new_tokens)
        dt = time.monotonic() - tik
        rate = spec.last_acceptance_rate
        print_summary(args, dt, out, label + " acceptance="
                      + (f"{rate:.2f}" if rate is not None else "n/a"))
        return
    if args.concurrent:
        if args.beams or args.monitor or args.prefill_ubatch:
            parser.error("--concurrent composes with greedy/sampled "
                         "generation only (not --beams/--monitor/"
                         "--prefill-ubatch)")
        from pipeedge_tpu.parallel.batcher import ContinuousBatcher
        handle = pipe.precompute_prefix(ids[:1, :p_len]) if p_len else None
        req_ids = ids[:, p_len:] if p_len else ids

        def run_batch():
            batcher = ContinuousBatcher(pipe)
            for req in range(args.concurrent):
                batcher.submit(req, req_ids, args.new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k, seed=args.seed + req,
                               prefix=handle)
            return batcher, batcher.run()

        run_batch()                      # compile programs
        tik = time.monotonic()
        batcher, results = run_batch()
        dt = time.monotonic() - tik
        n_tok = args.concurrent * args.batch_size * args.new_tokens
        shared = f", shared prefix {p_len}" if p_len else ""
        print(telemetry.startup_line())
        print(f"generated {args.concurrent}x{args.batch_size}x"
              f"{args.new_tokens} tokens in {dt:.3f}s = {n_tok / dt:.1f} "
              f"tok/s ({len(partition)} stages, continuous batching"
              f"{shared}; {batcher.stats['ticks']} ticks, "
              f"{batcher.stats['stage_steps']} stage-steps)")
        out0 = with_prefix(results[0]) if p_len else results[0]
        print("sample continuation ids:",
              out0[0, args.prompt_len:].tolist())
        return
    if args.beams:
        run = lambda n, cb=None: np.asarray(
            pipe.generate_beam(ids, n, beams=args.beams))
        label = f"{len(partition)} stages, beam {args.beams}"
    elif p_len:
        handle = pipe.precompute_prefix(ids[:1, :p_len])
        sample_kw = dict(temperature=args.temperature, top_k=args.top_k,
                         seed=args.seed)
        run = lambda n, cb=None: with_prefix(np.asarray(pipe.generate(
            ids[:, p_len:], n, step_callback=cb, prefix=handle,
            **sample_kw)))
        label = (f"{len(partition)} stages, shared prefix {p_len} "
                 "(prefilled once)")
    else:
        sample_kw = dict(temperature=args.temperature, top_k=args.top_k,
                         seed=args.seed, prefill_ubatch=args.prefill_ubatch)
        run = lambda n, cb=None: np.asarray(
            pipe.generate(ids, n, step_callback=cb, **sample_kw))
        label = f"{len(partition)} stages"
    run(min(2, args.new_tokens))   # compile programs
    tik = time.monotonic()
    out = run(args.new_tokens, heartbeat)
    dt = time.monotonic() - tik
    if args.monitor:
        import monitoring
        monitoring.finish()
    print_summary(args, dt, out, label,
                  () if args.beams else pipe.batch_accounts)


if __name__ == "__main__":
    main()
