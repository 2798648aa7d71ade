"""Profile-driven 8-stage scaling projection (round-5 verdict item 8).

Real multi-chip hardware is unavailable in this environment, so the
multi-chip throughput claim is projected from measured single-chip
inputs, all of them committed in-repo:

- per-sublayer forward times measured ON the real chip
  (profiles/tpu/device_types.yml, `make_tpu_profiles.sh` recipe);
- per-sublayer edge payload sizes (elements/sample) from the same
  profiling pass (profiles/tpu/models.yml);
- the partition chosen by the NATIVE scheduler (native/partition.cpp)
  for an N-device tpu-v5e fleet — the same binary/cost model users run.

Steady-state pipeline throughput is batch / max_stage_time. Two comm
scenarios bound the answer:
- `overlapped`: stage-edge transfers overlap the next microbatch's
  compute (the SPMD driver's ppermute rides ICI asynchronously inside
  one program — parallel/spmd.py), so t_stage = compute only;
- `serialized`: worst case, t_stage = compute + edge_in/bw — reported
  for BOTH the conservative 100 Gbps DCN planning number the committed
  device_types carry and a 1600 Gbps v5e ICI-class link.

The dryrun (`__graft_entry__.dryrun_multichip`) executes the actual
8-stage edge logic on a virtual mesh; this tool prices it with the
chip-measured numbers. Prints ONE JSON line; --markdown emits the
BASELINE.md section.
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROFILE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "profiles", "tpu")
ICI_MBPS = 1_600_000          # v5e ICI class (public spec sheet, per chip)
# img/s of the fused one-chip program, by (model, batch): deleted record of
# the former chip. Goes with this tool once a four-chip cell is measured.
FUSED_ANCHOR_IMG_PER_SEC = {("google/vit-large-patch16-224", 8): 946.845}


def project(model_name: str, n_devices: int, batch: int,
            dtype: str = "bfloat16"):
    import yaml

    from pipeedge_tpu.sched.scheduler import sched_pipeline

    with open(os.path.join(PROFILE_DIR, "models.yml")) as f:
        models = yaml.safe_load(f)
    with open(os.path.join(PROFILE_DIR, "device_types.yml")) as f:
        dev_types = yaml.safe_load(f)
    entry = models[model_name]
    prof = next(p for p in dev_types["tpu-v5e"]["model_profiles"]
                [model_name] if p["dtype"] == dtype
                and p["batch_size"] == batch)
    times = prof["time_s"]
    out_elems = entry["parameters_out"]
    dcn_mbps = dev_types["tpu-v5e"]["bw_Mbps"]

    with tempfile.NamedTemporaryFile("w", suffix=".yml",
                                     delete=False) as f:
        yaml.safe_dump({"tpu-v5e": [f"tpu{i}" for i in
                                    range(n_devices)]}, f)
        dev_file = f.name
    try:
        sched = sched_pipeline(
            model_name, 0, 0, batch, dtype=dtype,
            models_file=os.path.join(PROFILE_DIR, "models.yml"),
            dev_types_file=os.path.join(PROFILE_DIR, "device_types.yml"),
            dev_file=dev_file)
    finally:
        os.unlink(dev_file)
    partition = [next(iter(st.values())) for st in sched]

    stages = []
    for l, r in partition:
        compute = sum(times[l - 1:r])
        edge_elems = out_elems[r - 1]           # elements per sample out
        edge_bytes = edge_elems * batch * (2 if dtype == "bfloat16"
                                           else 4)
        stages.append({"layers": [l, r],
                       "compute_ms": round(compute * 1e3, 3),
                       "edge_out_mb": round(edge_bytes / 1e6, 2)})
    total_ms = sum(s["compute_ms"] for s in stages)

    def throughput(comm_mbps=None):
        """batch / steady-state max stage time (comm serialized into the
        stage when a bandwidth is given, overlapped when None)."""
        worst = 0.0
        for i, s in enumerate(stages):
            t = s["compute_ms"]
            if comm_mbps is not None and i > 0:
                in_mb = stages[i - 1]["edge_out_mb"]
                t += in_mb * 8 / comm_mbps * 1e3     # MB over Mbit/s
            worst = max(worst, t)
        return batch / (worst / 1e3), worst

    single = batch / (total_ms / 1e3)
    tp_overlap, worst_overlap = throughput(None)
    tp_dcn, worst_dcn = throughput(dcn_mbps)
    tp_ici, worst_ici = throughput(ICI_MBPS)

    # ABSOLUTE projection, anchored to the fused-program measurement:
    # the per-sublayer profile times carry per-call dispatch granularity
    # (each sublayer its own program), so their sum (-> `single` above)
    # is far below the fused single-chip bench (one scanned program). A
    # stage executes ITS sublayers as one fused program too, so the
    # absolute stage time is better estimated as the measured
    # fused microbatch time x the stage's PROFILE-TIME SHARE (the
    # profiles' relative balance is the measured quantity the scheduler
    # optimizes), plus the explicit edge cost.
    fused = None
    fused_img = FUSED_ANCHOR_IMG_PER_SEC.get((model_name, batch))
    if fused_img is not None:
        ubatch_ms = batch / fused_img * 1e3
        shares = [s["compute_ms"] / total_ms for s in stages]
        worst_share = max(shares)

        def fused_tp(comm_mbps):
            worst = 0.0
            for i, s in enumerate(stages):
                t = ubatch_ms * (s["compute_ms"] / total_ms)
                if comm_mbps is not None and i > 0:
                    t += stages[i - 1]["edge_out_mb"] * 8 \
                        / comm_mbps * 1e3
                worst = max(worst, t)
            return round(batch / (worst / 1e3), 1), round(worst, 3)

        fused = {
            "anchor_img_per_sec": fused_img,
            "anchor_ubatch_ms": round(ubatch_ms, 3),
            "worst_stage_share": round(worst_share, 4),
            "overlapped_comm": dict(zip(
                ("img_per_sec", "bottleneck_stage_ms"),
                fused_tp(None))),
            "serialized_ici_1600gbps": dict(zip(
                ("img_per_sec", "bottleneck_stage_ms"),
                fused_tp(ICI_MBPS))),
            "serialized_dcn_100gbps": dict(zip(
                ("img_per_sec", "bottleneck_stage_ms"),
                fused_tp(dcn_mbps))),
        }
        for k in ("overlapped_comm", "serialized_ici_1600gbps",
                  "serialized_dcn_100gbps"):
            fused[k]["speedup_vs_single"] = round(
                fused[k]["img_per_sec"] / fused_img, 2)
    return {
        "fused_anchor_projection": fused,
        "model": model_name, "n_devices": n_devices, "batch": batch,
        "dtype": dtype, "partition": partition, "stages": stages,
        "single_chip_img_per_sec": round(single, 1),
        "projected": {
            "overlapped_comm": {
                "img_per_sec": round(tp_overlap, 1),
                "bottleneck_stage_ms": worst_overlap,
                "speedup_vs_single": round(tp_overlap / single, 2)},
            "serialized_dcn_100gbps": {
                "img_per_sec": round(tp_dcn, 1),
                "bottleneck_stage_ms": round(worst_dcn, 3),
                "speedup_vs_single": round(tp_dcn / single, 2)},
            "serialized_ici_1600gbps": {
                "img_per_sec": round(tp_ici, 1),
                "bottleneck_stage_ms": round(worst_ici, 3),
                "speedup_vs_single": round(tp_ici / single, 2)},
        },
    }


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model-name",
                   default="google/vit-large-patch16-224")
    p.add_argument("-n", "--n-devices", default=8, type=int)
    p.add_argument("-b", "--batch", default=8, type=int)
    p.add_argument("--markdown", action="store_true",
                   help="emit the BASELINE.md section instead of JSON")
    args = p.parse_args()
    r = project(args.model_name, args.n_devices, args.batch)
    if not args.markdown:
        print(json.dumps(r))
        return
    pr = r["projected"]
    fa = r["fused_anchor_projection"]
    print(f"""### Projected {r['n_devices']}-stage scaling ({r['model']}, b={r['batch']}, chip-measured inputs)

Relative balance from the committed per-sublayer chip profiles + the
native scheduler's partition; absolute throughput anchored to the fused
single-chip bench (profile times carry per-sublayer dispatch
granularity, so their sum under-states a fused stage program):

| scenario | img/s | vs 1 chip (fused) | bottleneck stage |
|---|---|---|---|
| single chip, fused program (anchor: deleted record of the former chip) | {fa['anchor_img_per_sec']} | 1.0x | {fa['anchor_ubatch_ms']} ms |
| {r['n_devices']}-stage, comm overlapped (SPMD ppermute) | {fa['overlapped_comm']['img_per_sec']} | {fa['overlapped_comm']['speedup_vs_single']}x | {fa['overlapped_comm']['bottleneck_stage_ms']} ms |
| {r['n_devices']}-stage, comm serialized @ ICI 1600 Gbps | {fa['serialized_ici_1600gbps']['img_per_sec']} | {fa['serialized_ici_1600gbps']['speedup_vs_single']}x | {fa['serialized_ici_1600gbps']['bottleneck_stage_ms']} ms |
| {r['n_devices']}-stage, comm serialized @ DCN 100 Gbps | {fa['serialized_dcn_100gbps']['img_per_sec']} | {fa['serialized_dcn_100gbps']['speedup_vs_single']}x | {fa['serialized_dcn_100gbps']['bottleneck_stage_ms']} ms |

Profile-granularity cross-check (per-sublayer times summed, no fusion
correction): {pr['overlapped_comm']['speedup_vs_single']}x overlapped /
{pr['serialized_dcn_100gbps']['speedup_vs_single']}x @ 100 Gbps — the
speedup is insensitive to the anchor because the scheduler's partition
is balanced to {max(s['compute_ms'] for s in r['stages'])} ms worst
stage over {r['n_devices']} stages.

Partition (native scheduler, committed chip profiles): {r['partition']}""")


if __name__ == "__main__":
    main()
