"""Closed-loop rebalancing: the measured-cost DP solver, the policy
guardrails (hysteresis / cooldown / failover composition), the telemetry
digest plane it runs on, the adaptive microbatch planner, and the
measured-profile emission path.

The fleet tests at the bottom drive the acceptance scenario end to end: a
loopback DCN fleet with one chaos-delayed stage must rebalance under
`--rebalance auto` (and show the event in the trace report), while a
balanced fleet must never churn."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from pipeedge_tpu import telemetry
from pipeedge_tpu.parallel.pipeline import plan_microbatches
from pipeedge_tpu.sched import failover, profiles, rebalance
from pipeedge_tpu.telemetry import feedback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _est(stage, layer_s, emit_s, n=6):
    """A StageEstimate whose layer-proportional part splits evenly across
    dispatch/readback (how the DCN stage threads actually measure it)."""
    return feedback.StageEstimate(stage=stage, n=n, dispatch_s=layer_s / 2,
                                  readback_s=layer_s / 2, emit_s=emit_s)


# -- solver --------------------------------------------------------------

def test_solver_balanced_costs_even_split():
    part, bottleneck = rebalance.solve_partition([1.0] * 8, 2)
    assert part == [(1, 4), (5, 8)] and bottleneck == 4.0
    assert rebalance.solve_partition([1.0] * 12, 3)[0] == \
        [(1, 4), (5, 8), (9, 12)]


def test_solver_shifts_layers_off_expensive_region():
    # layers 1-2 cost 5x: stage 0 must carry fewer layers
    part, bottleneck = rebalance.solve_partition([5, 5, 1, 1, 1, 1, 1, 1], 2)
    assert part == [(1, 2), (3, 8)] and bottleneck == 10.0


def test_solver_fixed_costs_shrink_the_burdened_stage():
    # stage 1 pays a 10s per-microbatch fixed cost (slow link): the solver
    # hands it as few layers as possible — but knows it cannot remove the
    # fixed cost by handing it zero
    part, bottleneck = rebalance.solve_partition([1.0] * 8, 2,
                                                 fixed_costs=[0.0, 10.0])
    assert part == [(1, 7), (8, 8)] and bottleneck == 11.0


def test_solver_alignment_keeps_block_cuts():
    part, bottleneck = rebalance.solve_partition([3, 3, 3, 3, 1, 1, 1, 1],
                                                 2, align=4)
    assert part == [(1, 4), (5, 8)] and bottleneck == 12.0
    part16, _ = rebalance.solve_partition([1.0] * 16, 2,
                                          fixed_costs=[0.0, 5.0], align=4)
    for l, r in part16:
        assert (l - 1) % 4 == 0 and r % 4 == 0


def test_solver_rejects_impossible_splits():
    with pytest.raises(ValueError):
        rebalance.solve_partition([1.0] * 2, 3)
    with pytest.raises(ValueError):
        rebalance.solve_partition([1.0] * 6, 2, align=4)


def test_spread_layer_costs_uniform_per_stage():
    costs = rebalance.spread_layer_costs([(1, 2), (3, 8)], [4.0, 12.0])
    assert costs == [2.0, 2.0] + [2.0] * 6
    with pytest.raises(ValueError):
        rebalance.spread_layer_costs([(1, 2), (4, 8)], [1.0, 1.0])


def test_expand_partition_recuts_over_more_stages():
    """The healing side of the loop: a contracted partition re-expands
    over restored capacity with the same bottleneck-minimizing DP."""
    assert rebalance.expand_partition([(1, 8)], 2) == [(1, 4), (5, 8)]
    # measured costs steer the cut: a heavy tail gets the smaller range
    skewed = rebalance.expand_partition(
        [(1, 8)], 2, layer_costs=[1, 1, 1, 1, 1, 1, 4, 4])
    assert skewed[-1][0] > 5
    # alignment constraint (--stage-tp) holds through an expansion
    aligned = rebalance.expand_partition([(1, 16)], 2, align=4)
    for l, r in aligned:
        assert (l - 1) % 4 == 0 and r % 4 == 0


def test_expand_partition_rejects_non_expansions():
    with pytest.raises(ValueError):
        rebalance.expand_partition([(1, 4), (5, 8)], 2)   # not more stages
    with pytest.raises(ValueError):
        rebalance.expand_partition([], 2)
    with pytest.raises(ValueError):
        rebalance.expand_partition([(1, 8)], 2, layer_costs=[1.0] * 3)


# -- policy guardrails ---------------------------------------------------

def test_policy_balanced_fleet_is_noop():
    """Hysteresis: equal measured stages produce no proposal, ever."""
    pol = rebalance.RebalancePolicy(threshold=0.10, cooldown=1)
    part = [(1, 4), (5, 8)]
    ests = {0: _est(0, 0.040, 0.002), 1: _est(1, 0.040, 0.002)}
    for rnd in range(4):
        assert pol.consider(part, ests, rnd) is None
    assert pol.events == 0


def test_policy_slow_stage_shifts_layers_off_it():
    pol = rebalance.RebalancePolicy(threshold=0.10, cooldown=1, confirm=1)
    part = [(1, 4), (5, 8)]
    # stage 1: same compute, plus a 30ms per-microbatch emit stall
    ests = {0: _est(0, 0.040, 0.001), 1: _est(1, 0.040, 0.030)}
    # first actionable window: held for confirmation (one window of noise
    # must never re-partition the fleet)
    assert pol.consider(part, ests, 0) is None
    # the straggler persists: the second agreeing window acts
    prop = pol.consider(part, ests, 1)
    assert prop is not None and pol.events == 1
    assert prop.partition[0][1] > 4          # layers moved onto stage 0
    assert prop.gain >= 0.10
    assert prop.bottleneck_after_s < prop.bottleneck_before_s


def test_policy_confirmation_filters_flip_flopping_noise():
    """Windows that blame a DIFFERENT stage each round (drift noise, not
    a straggler) never accumulate the confirmation streak."""
    pol = rebalance.RebalancePolicy(threshold=0.05, cooldown=0, confirm=1)
    part = [(1, 4), (5, 8)]
    slow1 = {0: _est(0, 0.040, 0.001), 1: _est(1, 0.040, 0.030)}
    slow0 = {0: _est(0, 0.040, 0.030), 1: _est(1, 0.040, 0.001)}
    for rnd in range(6):
        assert pol.consider(part, slow1 if rnd % 2 == 0 else slow0,
                            rnd) is None
    assert pol.events == 0


def test_policy_min_gain_threshold_holds_partition():
    # a fixed 100ms stall dwarfs the 4ms of movable compute: predicted
    # relative gain is tiny, so a high threshold keeps the partition
    pol = rebalance.RebalancePolicy(threshold=0.10, cooldown=0, confirm=0)
    ests = {0: _est(0, 0.004, 0.001), 1: _est(1, 0.004, 0.100)}
    assert pol.consider([(1, 4), (5, 8)], ests, 0) is None
    assert pol.events == 0
    # the same measurements clear a permissive threshold
    pol2 = rebalance.RebalancePolicy(threshold=0.0, cooldown=0, confirm=0)
    assert pol2.consider([(1, 4), (5, 8)], ests, 0) is not None


def test_policy_cooldown_prevents_oscillation():
    pol = rebalance.RebalancePolicy(threshold=0.05, cooldown=2, confirm=0)
    part = [(1, 4), (5, 8)]
    slow1 = {0: _est(0, 0.040, 0.001), 1: _est(1, 0.040, 0.030)}
    prop = pol.consider(part, slow1, 0)
    assert prop is not None
    # next windows flip the imbalance (noise): cooldown holds the plan
    slow0 = {0: _est(0, 0.060, 0.030), 1: _est(1, 0.020, 0.001)}
    assert pol.consider(prop.partition, slow0, 1) is None
    assert pol.consider(prop.partition, slow0, 2) is None
    # cooldown expired: a persistent imbalance may act again
    assert pol.consider(prop.partition, slow0, 3) is not None
    assert pol.events == 2


def test_policy_settles_after_rebalancing():
    """Once the measured profile matches the new partition, re-solving
    reproduces it: no further proposals (convergence, not churn)."""
    pol = rebalance.RebalancePolicy(threshold=0.05, cooldown=0, confirm=0)
    prop = pol.consider([(1, 4), (5, 8)],
                        {0: _est(0, 0.040, 0.001),
                         1: _est(1, 0.040, 0.030)}, 0)
    assert prop is not None and prop.partition == [(1, 5), (6, 8)]
    settled = {0: _est(0, 0.050, 0.001), 1: _est(1, 0.030, 0.030)}
    assert pol.consider(prop.partition, settled, 1) is None
    assert pol.events == 1


def test_rebalance_composes_with_failover():
    """A death landing while a re-plan is pending: the failover planner
    must run on the PROPOSED partition (what the next round will
    broadcast) exactly as on a static one — spare substitution keeps the
    new cuts and moves the dead rank's stage to the spare."""
    pol = rebalance.RebalancePolicy(threshold=0.05, cooldown=0, confirm=0)
    prop = pol.consider([(1, 4), (5, 8)],
                        {0: _est(0, 0.040, 0.001),
                         1: _est(1, 0.040, 0.030)}, 0)
    assert prop is not None
    planned = failover.plan_failover(prop.partition, [0, 0], [0, 1],
                                     world_size=3, dead_ranks={1})
    assert planned is not None
    layers, quant, ranks = planned
    assert layers == prop.partition     # rebalanced cuts survive failover
    assert ranks == [0, 2]              # stage 1 moved onto the spare


# -- digest plane --------------------------------------------------------

def test_recorder_digest_accumulates_and_survives_ring_overflow():
    rec = telemetry.SpanRecorder(rank=0, capacity=4)
    for i in range(10):
        rec.record("stage", "dispatch", 0, 1000, stage=1)
    rec.record("feed", "mb0", 0, 500)          # not a digest category
    assert len(rec) == 4                       # ring dropped the oldest...
    dig = rec.digest()
    assert dig[("stage", "dispatch", 1)] == (10, 10_000)   # ...digest didn't
    assert ("feed", "mb0", None) not in dig


def test_digest_wire_roundtrip_and_diff():
    rec = telemetry.SpanRecorder(rank=2, capacity=16)
    rec.record("stage", "emit", 100, 400, stage=0)
    rec.record("wire", "send->r1", 0, 50)
    prev = rec.digest()
    assert telemetry.digest_from_wire(telemetry.digest_to_wire(prev)) == prev
    assert telemetry.digest_from_wire(np.zeros(0, np.uint8)) == {}
    rec.record("stage", "emit", 0, 100, stage=0)
    delta = feedback.diff_digests(rec.digest(), prev)
    assert delta == {("stage", "emit", 0): (1, 100)}
    # a restarted rank's fresh (smaller) counters fall back, never negative
    regressed = feedback.diff_digests(prev, rec.digest())
    assert all(n > 0 and ns >= 0 for n, ns in regressed.values())


def test_stage_estimates_from_merged_digests():
    d0 = {("stage", "dispatch", 0): (4, 8_000_000_000),
          ("stage", "readback", 0): (4, 4_000_000_000),
          ("stage", "emit", 0): (4, 2_000_000_000)}
    d1 = {("stage", "dispatch", 1): (4, 2_000_000_000),
          ("stage", "emit", 1): (4, 12_000_000_000),
          ("wire", "send->r0", None): (4, 1_000_000_000)}
    ests = feedback.stage_estimates(feedback.merge_digests([d0, d1]))
    assert ests[0].n == 4
    assert ests[0].layer_s == pytest.approx(3.0)   # (2 + 1) s/mb
    assert ests[0].fixed_s == pytest.approx(0.5)
    assert ests[1].service_s == pytest.approx(3.5)
    assert feedback.edge_estimates(d1) == {"send->r0": pytest.approx(0.25)}
    assert feedback.check_estimates(ests, 2) == []
    problems = feedback.check_estimates(ests, 3)
    assert any("stage 2" in p for p in problems)
    assert feedback.check_estimates(ests, 2, min_samples=5)
    assert feedback.check_estimates({0: ests[0]}, 1) == []
    stale = feedback.check_estimates({0: ests[0], 5: ests[1]}, 1)
    assert any("outside" in p for p in stale)


def test_digest_from_spans_matches_recorder_rollup():
    rec = telemetry.SpanRecorder(rank=0, capacity=64)
    rec.record("stage", "dispatch", 0, 3_000, stage=0)
    rec.record("compute", "stage0", 0, 1_000, stage=0)
    rec.record("runtime", "round0", 0, 9_000)      # not a digest category
    assert feedback.digest_from_spans(rec.snapshot()) == rec.digest()


# -- adaptive microbatch planner ----------------------------------------

def test_plan_microbatches_bubble_vs_overhead():
    # no per-microbatch overhead: finest split (bubble term dominates)
    u, m, _ = plan_microbatches(64, 4, t_item_s=0.01, t_fixed_s=0.0)
    assert (u, m) == (1, 64)
    # overhead dominates: one big microbatch
    u, m, _ = plan_microbatches(64, 4, t_item_s=1e-4, t_fixed_s=0.05)
    assert (u, m) == (64, 1)
    # single stage has no fill/drain bubble: overhead alone decides
    u, m, _ = plan_microbatches(64, 1, t_item_s=0.01, t_fixed_s=0.001)
    assert (u, m) == (64, 1)
    # the balanced case lands strictly between the extremes
    u, m, t = plan_microbatches(64, 4, t_item_s=0.01, t_fixed_s=0.01)
    assert 1 < u < 64 and m == -(-64 // u)
    assert t == pytest.approx((m + 3) * (0.01 + 0.01 * u))
    with pytest.raises(ValueError):
        plan_microbatches(0, 4, 0.01, 0.01)
    with pytest.raises(ValueError):
        plan_microbatches(8, 2, 0.01, 0.01, max_ubatch=0)


# -- measured-profile emission (sched/profiles.py ingestion) ------------

def test_measured_profiles_roundtrip_and_upsert(tmp_path):
    record = profiles.results_from_measured(
        "pipeedge/test-tiny-vit", "float32", 4, total_layers=8,
        partition=[(1, 6), (7, 8)], stage_times_s=[0.12, 0.08])
    times = [rec["time"] for rec in record["profile_data"]]
    assert times == pytest.approx([0.02] * 6 + [0.04] * 2)
    path = tmp_path / "live.yaml"
    profiles.save_measured_profiles(str(path), record)
    back = profiles.ProfilerResults.load(str(path))
    assert back.layers == 8 and back.batch_size == 4
    # the timing profile merges into a device_types.yml like any offline
    # profiler run (what "re-schedule from live measurements" consumes)
    dev_types = tmp_path / "device_types.yml"
    profiles.upsert_device_type(str(dev_types), "tpuv4", back,
                                mem_MB=1024, bw_Mbps=1000)
    import yaml
    loaded = yaml.safe_load(dev_types.read_text())
    prof = loaded["tpuv4"]["model_profiles"]["pipeedge/test-tiny-vit"][0]
    assert prof["time_s"] == pytest.approx(times)


def test_measured_profiles_reject_bad_partitions():
    with pytest.raises(profiles.ProfileError):
        profiles.results_from_measured("m", "float32", 4, total_layers=8,
                                       partition=[(1, 4)],
                                       stage_times_s=[1.0, 1.0])
    with pytest.raises(profiles.ProfileError):
        profiles.results_from_measured("m", "float32", 4, total_layers=9,
                                       partition=[(1, 4), (5, 8)],
                                       stage_times_s=[1.0, 1.0])


# -- fleet acceptance ----------------------------------------------------

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _run_rebalance_fleet(tmp_path, chaos, rebalance_mode="auto",
                         threshold=0.02):
    trace = tmp_path / "trace.json"
    addrs = ",".join(f"127.0.0.1:{p}" for p in _free_ports(2))
    common = [sys.executable, os.path.join(REPO, "runtime.py")]
    opts = ["-c", "dcn", "--platform", "cpu", "-m",
            "pipeedge/test-tiny-vit", "-pt", "1,4,5,8", "-b", "24",
            "-u", "4", "--dcn-addrs", addrs, "--sched-timeout", "120",
            "--rounds", "3", "--rebalance", rebalance_mode,
            "--rebalance-threshold", str(threshold),
            "--rebalance-cooldown", "0", "--trace-spans", str(trace)]
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               DCN_CONNECT_TIMEOUT="30")
    wenv = dict(env, DCN_CHAOS=chaos) if chaos else env
    # the worker's log goes to a file: a pipe nobody reads while rank 0
    # runs holds 64 KB, and a worker that has logged that much blocks
    with open(tmp_path / "worker.log", "w") as log:
        worker = subprocess.Popen(common + ["1", "2"] + opts, cwd=tmp_path,
                                  env=wenv, stdout=log,
                                  stderr=subprocess.STDOUT, text=True)
    try:
        data = subprocess.run(common + ["0", "2"] + opts, cwd=tmp_path,
                              env=env, capture_output=True, text=True,
                              timeout=300)
    finally:
        try:
            worker.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            worker.kill()
    assert data.returncode == 0, data.stdout + data.stderr
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace), "--require-spans"],
        capture_output=True, text=True, timeout=120)
    assert rep.returncode == 0, rep.stdout + rep.stderr
    return data.stdout + data.stderr, json.loads(rep.stdout)


@pytest.mark.fleet
@pytest.mark.slow
def test_fleet_rebalances_around_chaos_delayed_stage(tmp_path):
    """Acceptance: a chaos-delayed stage (delay@1:80 on rank 1's sends)
    makes the data rank re-solve the partition from the measured digests,
    shift layers OFF the slow rank at a round boundary, and finish all
    rounds; the merged trace records exactly the applied rebalances."""
    out, rep = _run_rebalance_fleet(tmp_path, chaos="delay@1:80")
    assert "rebalance_round=" in out
    # layers moved off the delayed stage: its range shrank below 4 layers
    import re
    part = re.search(r"rebalance_round=\d+ partition=(\d+),(\d+),(\d+),(\d+)",
                     out)
    assert part is not None, out
    l1, r1 = int(part.group(3)), int(part.group(4))
    assert r1 - l1 + 1 < 4, f"slow stage kept {r1 - l1 + 1} layers: {out}"
    assert rep["rebalance_events"] >= 1
    assert rep["bubble_pct"] is not None and rep["spans"] > 0


@pytest.mark.fleet
@pytest.mark.slow
def test_fleet_balanced_never_churns(tmp_path):
    """Zero-churn guard: the same fleet with NO injected slowness runs all
    rounds without a single rebalance event."""
    out, rep = _run_rebalance_fleet(tmp_path, chaos=None)
    assert "rebalance_round=" not in out
    assert rep["rebalance_events"] == 0
