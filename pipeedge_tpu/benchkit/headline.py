"""Headline recipes: the exact streamed pipeline bench + the standalone
quantized-collectives A/B (moved from the monolithic bench.py; the CLI
there is now a thin dispatcher over the benchkit registry).

`exact` prints the same record keys bench.py always printed (metric,
value, vs_baseline, mfu, fast_numerics, quant_collectives, ...) — they
ride the trajectory envelope as the merged `legacy` block, so BENCH
records stay backward-greppable while gaining the schema-versioned
envelope (scenario, config fingerprint, env stamp, noise-banded
throughput block) bench_report diffs on.

Method notes (unchanged from bench.py — docs/PERF.md):
- microbatches stream through ONE jitted `lax.scan` program; a scalar
  readback fences execution.
- the headline `value` is the MEDIAN img/s of REPS repetitions with
  min/max spread and raw samples in the record, so session drift is
  visible inside one line.
- MFU reports against BOTH denominators: the session-calibrated peak
  (pinned CALIBRATION_RECIPE, versioned) and the nominal device spec.
"""
import statistics
import time

BASELINE_IMG_PER_SEC = 0.22  # ViT-L b=8 on RCC-VE-C2000 (BASELINE.md)

REPS = 5  # timed repetitions of the streaming loop (median reported)

# Nominal dense bf16 peak FLOP/s by device kind (public TPU spec sheets).
# Used as the second MFU denominator; absent kinds report null.
NOMINAL_BF16_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


# The PINNED peak-TFLOP calibration recipe (round-5 verdict item 7).
# Version it; never change a field without bumping `version` — the MFU
# denominators of different BENCH records are only comparable within one
# recipe version. Per-session spread is recorded alongside every result
# so the ±% error bars on calibrated MFU are explicit in the record.
CALIBRATION_RECIPE = {
    "version": "cal-v1",
    "matmul_mnk": [8192, 8192, 8192],
    "chain_length": 32,
    "dtype": "bfloat16",
    "accumulate": "float32",
    "protocol": "one jitted lax.scan chain; 1 compile+warm call, then "
                "3 timed reps fenced by scalar readback; peak = best "
                "rep, spread = all reps",
}


def calibrate_peak_samples(m: int = None) -> list:
    """Per-rep implied bf16 FLOP/s (2*M*N*K) under CALIBRATION_RECIPE;
    the chain amortizes dispatch latency out of the measurement.
    max(samples) is the session peak; the spread IS the error bar on
    every calibrated-MFU number this session. A non-default `m`
    (--cal-dim, CPU-loopback A/B runs) is off-recipe: its MFU numbers
    are marked and never comparable across records."""
    import jax
    import jax.numpy as jnp
    if m is None:
        m = CALIBRATION_RECIPE["matmul_mnk"][0]
    k_iters = CALIBRATION_RECIPE["chain_length"]
    a = jnp.ones((m, m), jnp.bfloat16)
    b = jnp.ones((m, m), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        def step(c, _):
            y = jnp.dot(c, b, preferred_element_type=jnp.float32)
            return y.astype(jnp.bfloat16) * 1e-4, None

        out, _ = jax.lax.scan(step, a, None, length=k_iters)
        return jnp.sum(out.astype(jnp.float32))

    float(mm(a, b))  # compile + warm
    samples = []
    for _ in range(3):
        tik = time.monotonic()
        float(mm(a, b))
        samples.append(2 * k_iters * m**3 / (time.monotonic() - tik))
    return samples


def calibrate_peak_flops() -> float:
    """Session peak FLOP/s under the pinned recipe (best rep)."""
    return max(calibrate_peak_samples())


def model_flops_per_image(cfg) -> float:
    """Analytic ViT forward FLOPs per image (2*MAC convention)."""
    s = cfg.num_patches + 1
    d, i, l = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    per_block = 8 * s * d * d + 4 * s * s * d + 4 * s * d * i
    embed = 2 * s * (cfg.patch_size**2 * cfg.num_channels) * d
    head = 2 * d * max(cfg.num_labels, 1)
    return l * per_block + embed + head


def top1_agreement(logits_exact, logits_var) -> dict:
    """The accuracy-delta fields EVERY non-exact bench variant reports
    beside its throughput (fast_numerics, quant_collectives, ...): a
    non-exact number without its agreement is not self-describing."""
    import numpy as np
    return {
        "top1_agreement_vs_exact": round(float(np.mean(
            np.argmax(logits_exact, -1) == np.argmax(logits_var, -1))), 4),
        "max_abs_logit_delta": round(
            float(np.max(np.abs(logits_exact - logits_var))), 4),
    }


def quant_collectives_ab(name, bits: int, xs, flops_img: float,
                         peak_flops: float, nominal_peak) -> dict:
    """A/B for the quantized-ICI-collectives claim: the SAME streamed TP
    run with exact full-width psums vs int`bits` quantized collectives
    (ops/qcollectives.py qpsum at every Megatron psum site in
    parallel/tensor.py), interleaved rounds so session drift hits both
    sides equally. Reports img/s for both, the speedup quotient, the
    top-1 agreement + max-abs logit delta vs the exact side, and the
    traced wire footprint (docs/QUANT_COLLECTIVES.md).

    Needs >= 2 devices on the TP axis — a single-device backend has no
    ICI collective site to quantize, and the block says so instead of
    reporting a vacuous measurement."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..models import registry
    from ..ops import qcollectives
    from ..parallel import tensor as tp
    from ..utils import jax_compat

    entry = registry.get_model_entry(name)
    cfg = entry.config
    devs = jax.devices()
    n_tp, d = 1, 2
    while (d <= len(devs) and cfg.num_attention_heads % d == 0
           and cfg.intermediate_size % d == 0 and cfg.kv_heads % d == 0):
        n_tp, d = d, d * 2
    if n_tp < 2:
        return {"mode": "skipped", "bits": bits,
                "reason": f"{len(devs)} device(s) available: no ICI "
                          "collective sites (the TP axis needs >= 2 "
                          "devices dividing the head/FFN dims)"}
    _, params, _ = registry.module_shard_factory(
        name, None, 1, registry.get_model_layers(name),
        dtype=jnp.bfloat16, unroll=True)
    mesh = Mesh(np.asarray(devs[:n_tp]), ("tp",))
    blocks = tuple(tp.shard_block_params(cfg, bp, mesh)
                   for bp in params["blocks"])
    family = entry.family
    embed_p = jax.device_put(params.get("embeddings"))
    final_p = jax.device_put(params.get("final"))
    specs, local = tp.family_tp_plan(cfg)

    def build_and_warm(mode_bits: int):
        # the collective bitwidth is a trace-time flag: pin it across the
        # fresh shard_map body + jit wrapper AND their first (tracing)
        # call, then restore exact for everything else in this process
        tp.set_tp_quant_bits(mode_bits)
        try:
            body = jax_compat.shard_map(
                partial(local, cfg=cfg, axis="tp"), mesh=mesh,
                in_specs=(specs, P()), out_specs=P())

            @jax.jit
            def run_all(ep, fp, bps, xs):
                def step(carry, x):
                    h = family.embed(ep, x, cfg)
                    for bp in bps:
                        h = body(bp, h)
                    logits = family.finalize(fp, h, cfg)
                    return carry + jnp.sum(logits.astype(jnp.float32)), None

                total, _ = jax.lax.scan(step, jnp.float32(0), xs)
                return total

            @jax.jit
            def run_one(ep, fp, bps, x):
                h = family.embed(ep, x, cfg)
                for bp in bps:
                    h = body(bp, h)
                return family.finalize(fp, h, cfg)

            logits = np.asarray(run_one(embed_p, final_p, blocks,
                                        xs[0]).astype(jnp.float32))
            # run_one traced the SAME psum sites run_all is about to: drop
            # its tally entries so the wire accounting below counts each
            # site once, with run_all's execution multiplier
            qcollectives.reset_trace_tally()
            float(run_all(embed_p, final_p, blocks, xs))   # compile + warm
        finally:
            tp.set_tp_quant_bits(0)
        return run_all, logits

    n_ubatch, batch = xs.shape[0], xs.shape[1]
    run_exact, logits_exact = build_and_warm(0)
    run_q, logits_q = build_and_warm(bits)
    q_times, exact_times = [], []
    for _ in range(3):
        tik = time.monotonic()
        float(run_exact(embed_p, final_p, blocks, xs))
        exact_times.append(time.monotonic() - tik)
        tik = time.monotonic()
        float(run_q(embed_p, final_p, blocks, xs))
        q_times.append(time.monotonic() - tik)
    q_img = statistics.median(n_ubatch * batch / t for t in q_times)
    exact_img = statistics.median(n_ubatch * batch / t for t in exact_times)
    # per-run executions of each traced qpsum site: the block loop is
    # unrolled, so every site runs once per scan step (per microbatch)
    # over 1 warm + 3 timed run_all calls; run_one's single execution per
    # site was dropped from the tally above (one logits probe, < 1% of
    # the streamed traffic)
    collectives = qcollectives.record_collectives(
        executions=4 * n_ubatch)
    q_achieved = q_img * flops_img
    return {
        "mode": "tp-shard-map",
        "bits": bits,
        "tp": n_tp,
        "images_per_sec": round(q_img, 3),
        "exact_interleaved_images_per_sec": round(exact_img, 3),
        "speedup_vs_exact": round(q_img / exact_img, 3),
        "mfu_calibrated": round(q_achieved / peak_flops, 3),
        "mfu_nominal": (round(q_achieved / nominal_peak, 3)
                        if nominal_peak else None),
        "achieved_tflops": round(q_achieved / 1e12, 1),
        **top1_agreement(logits_exact, logits_q),
        "collectives": collectives,
    }


def _image_inputs(name, parser_error, n_ubatch: int, batch: int = 8):
    """(cfg, metric name, device-resident [U, B, C, H, W] input set) for
    an image-family model — the shared setup of both headline recipes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import registry
    entry = registry.get_model_entry(name)
    family_name = entry.family.FAMILY.name
    if family_name not in ("vit", "deit"):
        # the streamed loop builds pixel inputs from patch geometry and
        # the TP A/B assumes the dense column/row kernel plan — token
        # families would crash mid-bench after the compile time is spent
        parser_error(f"--model must be an image family (vit/deit) for "
                     f"this bench; {name} is family '{family_name}'")
    metric = ("vit_large_images_per_sec_b8"
              if name == "google/vit-large-patch16-224"
              else f"{name.rsplit('/', 1)[-1].replace('-', '_')}"
                   "_images_per_sec_b8")
    cfg = entry.config
    rng = np.random.default_rng(0)
    side = int(round(cfg.num_patches ** 0.5)) * cfg.patch_size
    xs = jax.device_put(jnp.asarray(
        rng.normal(size=(n_ubatch, batch, cfg.num_channels, side, side)),
        dtype=jnp.bfloat16))
    return cfg, metric, xs


def _common_args(p) -> None:
    p.add_argument("--model", default="google/vit-large-patch16-224",
                   help="model to bench (default: the ViT-L headline; "
                        "non-default models re-name the metric)")
    p.add_argument("--ubatches", type=int, default=128,
                   help="microbatches in the streamed set (128 amortizes "
                        "dispatch overhead on TPU; lower for CPU-"
                        "loopback A/B evidence runs)")
    p.add_argument("--tp-quant-bits", type=int, default=8, choices=[8, 4],
                   help="bitwidth of the quant_collectives variant "
                        "(runtime.py --tp-quant-bits; "
                        "docs/QUANT_COLLECTIVES.md)")
    p.add_argument("--cal-dim", type=int,
                   default=CALIBRATION_RECIPE["matmul_mnk"][0],
                   help="calibration matmul dimension; non-default "
                        "values are off-recipe (MFU marked, not "
                        "comparable across records) — for CPU-loopback "
                        "A/B runs where 8192^3 is infeasible")


def _exact_args(p) -> None:
    _common_args(p)
    p.add_argument("--reps", type=int, default=REPS,
                   help="timed repetitions (median reported)")


def run_exact(args) -> dict:
    """The headline record (bench.py's historical main), returned as
    trajectory blocks: envelope throughput/latency/mfu + the full legacy
    record merged at top level."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import telemetry
    from ..models import registry
    from ..models.layers import set_fast_numerics
    from ..monitoring.energy import default_energy_source
    from ..telemetry import report as span_report

    # Pin exact numerics for the headline/calibration passes BEFORE any
    # trace: an inherited PIPEEDGE_FAST_NUMERICS=1 would otherwise compile
    # the "exact" side of the A/B in fast mode too, reporting a ~1.0
    # speedup while claiming exact-parity numerics (ADVICE.md r5).
    set_fast_numerics(False)

    name = args.model
    batch = 8   # reference profiles use batch=8 (README_Scheduler.md)
    n_ubatch = args.ubatches

    def parser_error(msg):
        raise SystemExit(f"bench.py --recipe exact: {msg}")

    cfg, metric, xs = _image_inputs(name, parser_error, n_ubatch, batch)
    fn, params, _ = registry.module_shard_factory(
        name, None, 1, registry.get_model_layers(name), dtype=jnp.bfloat16)
    params = jax.device_put(params)

    cal_samples = calibrate_peak_samples(args.cal_dim)
    peak_flops = max(cal_samples)

    # the UN-jitted shard apply: the factory's fn is jitted, and jit
    # caches by function identity — a numerics-mode change (trace-time
    # flag) only binds through a fresh trace of the raw callable
    raw_fn = fn.__wrapped__

    def make_run_all():
        # a FRESH jit wrapper (and fresh inner trace via raw_fn) per
        # numerics mode
        @jax.jit
        def run_all(p, xs):
            def step(carry, x):
                logits = raw_fn(p, x)
                return carry + jnp.sum(logits.astype(jnp.float32)), None

            total, _ = jax.lax.scan(step, jnp.float32(0), xs)
            return total

        return run_all

    run_all = make_run_all()

    # Host-side energy (reference's energy-first monitoring demo): RAPL
    # powercap when readable, else an explicit unreadable record — never
    # silent omission.
    energy_src = default_energy_source()
    if energy_src is not None:
        energy_src.init()

    float(run_all(params, xs))  # compile + warmup (readback fences)
    e0 = energy_src.get_uj() if energy_src is not None else 0
    times = []
    for _ in range(args.reps):
        tik = time.monotonic()
        float(run_all(params, xs))
        times.append(time.monotonic() - tik)
    e1 = energy_src.get_uj() if energy_src is not None else 0
    samples = sorted(n_ubatch * batch / t for t in times)
    img_per_sec = statistics.median(samples)
    if energy_src is not None:
        wall = sum(times)
        energy_fields = {
            "host_energy_j_per_image": round(
                (e1 - e0) / 1e6 / (args.reps * n_ubatch * batch), 4),
            "host_power_w": round((e1 - e0) / 1e6 / wall, 1),
            "energy_source": "rapl-powercap (host CPU packages; TPU chip "
                             "power not exposed through JAX)",
        }
        energy_src.finish()
    else:
        energy_fields = {
            "energy_source": "unreadable on this host (no readable RAPL "
                             "powercap domains)"}

    # p50 microbatch latency: individual dispatch, fenced per microbatch.
    # Segmented (dispatch / transfer / emit) through telemetry spans so
    # the medians come out of the same span machinery the DCN trace
    # reports use.
    @jax.jit
    def run_one(p, x):
        return jnp.sum(fn(p, x).astype(jnp.float32))

    float(run_one(params, xs[0]))  # compile + warm
    rec = telemetry.configure(rank=0)
    lats = []
    for i in range(n_ubatch):
        tik = time.monotonic()
        with telemetry.span("stage", "dispatch", mb=i):
            fut = run_one(params, xs[i])
        with telemetry.span("stage", "transfer", mb=i):
            fut.block_until_ready()
        with telemetry.span("stage", "emit", mb=i):
            float(fut)
        lats.append(time.monotonic() - tik)
    segments = span_report.segment_medians(rec.snapshot(),
                                           cats=frozenset(("stage",)))
    telemetry.disable()
    p50_ms = statistics.median(lats) * 1e3
    steady_lats = sorted(lats[1:])
    latency_breakdown = {
        # first measured microbatch vs the warm rest: the fill/steady
        # split BENCH rounds track against steady_state_ubatch_ms
        "fill_ms": round(lats[0] * 1e3, 2),
        "steady_p50_ms": round(
            span_report.percentile(steady_lats, 50) * 1e3, 2),
        "steady_p99_ms": round(
            span_report.percentile(steady_lats, 99) * 1e3, 2),
        "segments_p50_ms": {
            key.split("/", 1)[1]: val["p50_ms"]
            for key, val in segments.items()},
    }

    flops_img = model_flops_per_image(cfg)
    achieved = img_per_sec * flops_img

    device_kind = jax.devices()[0].device_kind
    nominal_peak = NOMINAL_BF16_PEAK.get(device_kind)

    # fast-numerics headline (round-5 verdict item 1): the SAME streamed
    # loop with model-dtype LayerNorm/softmax and tanh GeLU, measured
    # interleaved with exact rounds so session drift hits both equally
    logits_exact = np.asarray(
        jax.jit(lambda p, x: raw_fn(p, x))(params,
                                           xs[0]).astype(jnp.float32))
    set_fast_numerics(True)
    try:
        run_all_fast = make_run_all()
        float(run_all_fast(params, xs))          # compile + warm
        fast_times, exact_times = [], []
        for _ in range(3):
            tik = time.monotonic()
            float(run_all(params, xs))
            exact_times.append(time.monotonic() - tik)
            tik = time.monotonic()
            float(run_all_fast(params, xs))
            fast_times.append(time.monotonic() - tik)
        fast_img_per_sec = statistics.median(
            n_ubatch * batch / t for t in fast_times)
        exact_adjacent = statistics.median(
            n_ubatch * batch / t for t in exact_times)
        logits_fast = np.asarray(
            jax.jit(lambda p, x: raw_fn(p, x))(params,
                                               xs[0]).astype(jnp.float32))
    finally:
        # None would re-defer to the env var — this bench's records must
        # stay exact-mode regardless of the inherited environment
        set_fast_numerics(False)
    fast_achieved = fast_img_per_sec * flops_img
    fast_fields = {
        "images_per_sec": round(fast_img_per_sec, 3),
        "exact_interleaved_images_per_sec": round(exact_adjacent, 3),
        "speedup_vs_exact": round(fast_img_per_sec / exact_adjacent, 3),
        "mfu_calibrated": round(fast_achieved / peak_flops, 3),
        "mfu_nominal": (round(fast_achieved / nominal_peak, 3)
                        if nominal_peak else None),
        "achieved_tflops": round(fast_achieved / 1e12, 1),
        **top1_agreement(logits_exact, logits_fast),
    }

    # quantized-collectives A/B: exact math, quantized ICI comms — the
    # variant meant to land between the exact and fast-numerics
    # endpoints at near-1.0 agreement
    qc_fields = quant_collectives_ab(name, args.tp_quant_bits, xs,
                                     flops_img, peak_flops, nominal_peak)

    off_recipe = args.cal_dim != CALIBRATION_RECIPE["matmul_mnk"][0]
    legacy = {
        "metric": metric,
        "value": round(img_per_sec, 3),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 1),
        "value_median": round(img_per_sec, 3),
        "value_spread": [round(samples[0], 3), round(samples[-1], 3)],
        "value_samples": [round(s, 3) for s in samples],
        "p50_microbatch_latency_ms": round(p50_ms, 2),
        "latency_breakdown": latency_breakdown,
        "steady_state_ubatch_ms": round(min(times) / n_ubatch * 1e3, 2),
        "mfu": round(achieved / peak_flops, 3),
        "mfu_calibrated": round(achieved / peak_flops, 3),
        "mfu_nominal": (round(achieved / nominal_peak, 3)
                        if nominal_peak else None),
        "achieved_tflops": round(achieved / 1e12, 1),
        # both names kept: calibrated_peak_tflops is the original record
        # key, peak_calibrated_tflops pairs with peak_nominal
        "calibrated_peak_tflops": round(peak_flops / 1e12, 1),
        "peak_calibrated_tflops": round(peak_flops / 1e12, 1),
        "peak_nominal_tflops": (round(nominal_peak / 1e12, 1)
                                if nominal_peak else None),
        # pinned calibration recipe + per-session spread (verdict item
        # 7): calibrated MFU carries explicit error bars
        "calibration": dict(
            CALIBRATION_RECIPE,
            matmul_mnk=[args.cal_dim] * 3,
            off_recipe=off_recipe or None,
            session_samples_tflops=[round(s / 1e12, 1)
                                    for s in cal_samples],
            calibration_spread=[round(min(cal_samples) / 1e12, 1),
                                round(max(cal_samples) / 1e12, 1)]),
        "mfu_calibrated_range": [
            round(achieved / max(cal_samples), 3),
            round(achieved / min(cal_samples), 3)],
        "fast_numerics": fast_fields,
        "quant_collectives": qc_fields,
        # the active collective bitwidth rides the record so BENCH_r0N
        # trajectories are self-describing (which knob produced this line)
        "tp_quant_bits": args.tp_quant_bits,
        "device_kind": device_kind,
        **energy_fields,
    }
    return {
        "throughput": {"value": legacy["value"], "unit": "images/sec",
                       "samples": legacy["value_samples"],
                       "spread": legacy["value_spread"]},
        "latency_ms": {"p50": latency_breakdown["steady_p50_ms"],
                       "p99": latency_breakdown["steady_p99_ms"],
                       "n": len(steady_lats)},
        "mfu": {"calibrated": legacy["mfu_calibrated"],
                "nominal": legacy["mfu_nominal"],
                "achieved_tflops": legacy["achieved_tflops"],
                "peak_calibrated_tflops":
                    legacy["peak_calibrated_tflops"],
                "calibration_version": CALIBRATION_RECIPE["version"],
                "off_recipe": off_recipe},
        "legacy": legacy,
    }


def _qc_args(p) -> None:
    _common_args(p)


def run_quant_collectives(args) -> dict:
    """Standalone quantized-collectives record (the exact recipe embeds
    the same A/B; this recipe re-arms just that scenario without paying
    the full headline run)."""
    import jax

    from ..models.layers import set_fast_numerics

    set_fast_numerics(False)

    def parser_error(msg):
        raise SystemExit(f"bench.py --recipe quant_collectives: {msg}")

    cfg, metric, xs = _image_inputs(args.model, parser_error,
                                    args.ubatches)
    cal_samples = calibrate_peak_samples(args.cal_dim)
    peak_flops = max(cal_samples)
    nominal_peak = NOMINAL_BF16_PEAK.get(jax.devices()[0].device_kind)
    qc = quant_collectives_ab(args.model, args.tp_quant_bits, xs,
                              model_flops_per_image(cfg), peak_flops,
                              nominal_peak)
    if qc.get("mode") == "skipped":
        return {"extras": qc,
                "notes": f"skipped: {qc['reason']}"}
    quality = {"top1_agreement_vs_exact": qc["top1_agreement_vs_exact"],
               "max_abs_logit_delta": qc["max_abs_logit_delta"]}
    return {
        "throughput": {"value": qc["images_per_sec"],
                       "unit": "images/sec"},
        "quality": quality,
        "mfu": {"calibrated": qc["mfu_calibrated"],
                "nominal": qc["mfu_nominal"],
                "achieved_tflops": qc["achieved_tflops"],
                "calibration_version": CALIBRATION_RECIPE["version"],
                "off_recipe": (args.cal_dim
                               != CALIBRATION_RECIPE["matmul_mnk"][0])},
        "extras": qc,
    }


def _register():
    from . import Recipe, register
    register(Recipe(
        "exact", "headline streamed-pipeline bench: exact img/s, "
                 "calibrated MFU, fast-numerics + quant-collectives A/Bs",
        _exact_args, run_exact, tier="chip"))
    register(Recipe(
        "quant_collectives", "standalone int8/int4 quantized-ICI-"
                             "collective A/B (needs tp >= 2 devices)",
        _qc_args, run_quant_collectives, tier="fast"))


_register()
