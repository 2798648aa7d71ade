"""The capacity controller's decision logic (pipeedge_tpu/serving/
autoscale.py): confirm streaks, dwell hysteresis, cooldown + flap
damper, brownout ordering, dry-run held transitions, advise-vs-auto
parity, and bound behaviour — all under the injected clock, no fleet
(ISSUE 20's unit matrix; the process-level acceptance lives in
tools/chaos_dcn.py --target autoscale and the CI autoscale-chaos job).
"""
import pytest

from pipeedge_tpu.serving.autoscale import (AutoscaleRunner,
                                            CapacityController,
                                            CapacityPolicy, DIRECTIONS,
                                            MODES, OUTCOMES,
                                            default_classify,
                                            signals_from_fleet)


HOT = {"queue_depth": 100.0, "brownout_level": 0, "burn_rate": 0.0}
COLD = {"queue_depth": 0.0, "brownout_level": 0, "burn_rate": 0.0}
NEUTRAL = {"queue_depth": 2.0, "brownout_level": 0, "burn_rate": 0.5}


def _ctl(size=1, mode="auto", plan_ok=True, **kw):
    """Controller over a mutable fake fleet: auto-apply mutates size."""
    kw.setdefault("min_size", 1)
    kw.setdefault("max_size", 3)
    kw.setdefault("confirm", 2)
    kw.setdefault("cooldown_s", 5.0)
    state = {"size": size, "applied": []}

    def plan_fn(direction, frm, to):
        if not plan_ok:
            return {"ok": False, "reason": "floor"}
        return {"ok": True, "direction": direction, "from": frm, "to": to}

    def apply_fn(plan):
        state["size"] = plan["to"]
        state["applied"].append(plan)

    ctl = CapacityController(CapacityPolicy(**kw), mode=mode,
                             size_fn=lambda: state["size"],
                             plan_fn=plan_fn, apply_fn=apply_fn)
    return ctl, state


def _drive(ctl, signals, n, t0=0.0, dt=1.0):
    """Tick `n` windows of `signals`; return decisions fired + end time."""
    out, t = [], t0
    for _ in range(n):
        d = ctl.tick(signals, now=t)
        if d is not None:
            out.append(d)
        t += dt
    return out, t


# ---------------------------------------------------------------------------
# policy validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(min_size=0), dict(min_size=3, max_size=2), dict(confirm=0),
    dict(cooldown_s=-1), dict(dwell_up_s=-1),
    dict(queue_low=5.0, queue_high=4.0), dict(queue_low=-1.0),
    dict(burn_low=2.0, burn_high=1.0), dict(flap_cap=0.5),
])
def test_policy_rejects_bad_knobs(bad):
    with pytest.raises(ValueError):
        CapacityPolicy(**bad)


def test_controller_rejects_bad_mode():
    with pytest.raises(ValueError):
        CapacityController(mode="yolo")
    assert set(MODES) == {"off", "advise", "auto"}
    assert set(DIRECTIONS) == {"up", "down"}
    assert set(OUTCOMES) == {"applied", "advised", "held", "flap_damped"}


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def test_classify_signs():
    pol = CapacityPolicy(queue_high=4, queue_low=0.5, burn_high=1.0,
                         burn_low=0.25)
    up = dict(HOT, size=1)
    assert default_classify(pol, up) == 1
    assert default_classify(pol, dict(COLD, size=1)) == -1
    assert default_classify(pol, dict(NEUTRAL, size=1)) == 0
    # brownout rung alone is up pressure
    assert default_classify(pol, dict(COLD, brownout_level=1, size=1)) == 1
    # burn alone is up pressure
    assert default_classify(pol, dict(COLD, burn_rate=2.0, size=1)) == 1
    # queue is per capacity unit: depth 6 over 4 units is calm
    assert default_classify(pol, {"queue_depth": 6.0, "brownout_level": 0,
                                  "burn_rate": 0.0, "size": 4}) == 0


# ---------------------------------------------------------------------------
# confirm + dwell hysteresis
# ---------------------------------------------------------------------------

def test_single_hot_window_moves_nothing():
    ctl, state = _ctl(confirm=3)
    assert ctl.tick(HOT, now=0.0) is None
    assert ctl.tick(NEUTRAL, now=1.0) is None     # streak broken
    assert ctl.tick(HOT, now=2.0) is None
    assert ctl.tick(HOT, now=3.0) is None
    assert state["size"] == 1                     # never reached confirm=3


def test_confirmed_pressure_scales_up():
    ctl, state = _ctl(confirm=2)
    fired, _ = _drive(ctl, HOT, 2)
    assert [d.outcome for d in fired] == ["applied"]
    assert fired[0].direction == "up"
    assert (fired[0].frm, fired[0].to) == (1, 2)
    assert state["size"] == 2
    assert "autoscale_decision direction=up" in fired[0].line()


def test_streak_resets_on_direction_change():
    ctl, state = _ctl(confirm=2)
    assert ctl.tick(HOT, now=0.0) is None
    assert ctl.tick(COLD, now=1.0) is None        # reversal resets streak
    assert ctl.tick(HOT, now=2.0) is None         # streak = 1 again
    assert state["size"] == 1


def test_dwell_blocks_until_streak_has_lasted():
    ctl, state = _ctl(confirm=2, dwell_up_s=10.0)
    fired, t = _drive(ctl, HOT, 5, dt=1.0)        # 5s of streak < 10s dwell
    assert fired == [] and state["size"] == 1
    fired, _ = _drive(ctl, HOT, 7, t0=t, dt=1.0)  # streak age crosses 10s
    assert [d.outcome for d in fired] == ["applied"]


def test_dwell_down_independent_of_dwell_up():
    ctl, state = _ctl(size=2, confirm=1, dwell_up_s=0.0, dwell_down_s=30.0)
    fired, _ = _drive(ctl, COLD, 10, dt=1.0)
    assert fired == [] and state["size"] == 2     # down dwell not served
    fired, _ = _drive(ctl, HOT, 1, t0=100.0)      # up fires immediately
    assert [d.direction for d in fired] == ["up"]


# ---------------------------------------------------------------------------
# bounds: steady state at the floor/ceiling is NOT a decision
# ---------------------------------------------------------------------------

def test_zero_decisions_at_floor_on_cold_fleet():
    ctl, state = _ctl(size=1, confirm=1, cooldown_s=0.0)
    fired, _ = _drive(ctl, COLD, 50)
    assert fired == []                            # the steady control run
    assert state["size"] == 1
    assert ctl.snapshot()["decisions"] == {o: 0 for o in OUTCOMES}


def test_zero_decisions_at_ceiling_under_pressure():
    ctl, state = _ctl(size=3, confirm=1, cooldown_s=0.0, max_size=3)
    fired, _ = _drive(ctl, HOT, 20)
    assert fired == [] and state["size"] == 3


# ---------------------------------------------------------------------------
# cooldown + flap damper
# ---------------------------------------------------------------------------

def test_cooldown_spaces_decisions():
    ctl, state = _ctl(confirm=1, cooldown_s=10.0)
    assert ctl.tick(HOT, now=0.0).outcome == "applied"   # 1 -> 2
    fired, _ = _drive(ctl, HOT, 9, t0=1.0)               # inside cooldown
    assert fired == [] and state["size"] == 2
    assert ctl.tick(HOT, now=11.0).outcome == "applied"  # 2 -> 3
    assert state["size"] == 3


def test_reversal_doubles_cooldown_and_renders_flap_damped():
    ctl, state = _ctl(confirm=1, cooldown_s=10.0)
    assert ctl.tick(HOT, now=0.0).direction == "up"      # 1 -> 2
    d = ctl.tick(COLD, now=11.0)                         # reversal: 2 -> 1
    assert d.direction == "down" and state["size"] == 1
    assert ctl.flap_factor == 2.0                        # damper armed
    # next reversal confirmed at t=22 — past cooldown_s but inside the
    # doubled window (11 + 10*2 = 31): renders flap_damped, moves nothing
    d = ctl.tick(HOT, now=22.0)
    assert d is not None and d.outcome == "flap_damped"
    assert (d.frm, d.to) == (1, 1) and state["size"] == 1
    # flap_damped emits once per streak episode, then stays quiet
    assert ctl.tick(HOT, now=23.0) is None
    # past the doubled window the decision goes through
    d = ctl.tick(HOT, now=40.0)
    assert d.outcome == "applied" and state["size"] == 2


def test_flap_factor_caps_and_calms():
    ctl, state = _ctl(confirm=1, cooldown_s=1.0, flap_cap=4.0)
    t = 0.0
    for sig in (HOT, COLD, HOT, COLD, HOT):              # ping-pong
        while ctl.tick(sig, now=t) is None or False:
            t += 1.0
        t += 100.0                                       # clear any damping
    assert ctl.flap_factor == 4.0                        # capped, not 16
    # two same-direction moves calm the damper back to 1
    ctl.tick(COLD, now=t)
    t += 100.0
    state["size"] = 3
    ctl.tick(COLD, now=t)
    assert ctl.flap_factor == 1.0


# ---------------------------------------------------------------------------
# brownout ordering: never shed capacity while the ladder sheds work
# ---------------------------------------------------------------------------

def test_scale_down_ordered_behind_brownout():
    ctl, state = _ctl(size=2, confirm=1, cooldown_s=0.0)
    # classifier would say "down" on these numbers if rung were 0, but a
    # custom classify_fn cannot smuggle a shed past an active ladder
    ctl._classify = lambda pol, sig: -1
    browned = dict(COLD, brownout_level=2)
    fired, _ = _drive(ctl, browned, 10)
    assert fired == [] and state["size"] == 2
    fired, _ = _drive(ctl, COLD, 1, t0=100.0)            # rung 0: sheds
    assert [d.direction for d in fired] == ["down"]


# ---------------------------------------------------------------------------
# dry-run plan -> held; apply failure -> held
# ---------------------------------------------------------------------------

def test_unrunnable_plan_renders_held():
    ctl, state = _ctl(size=2, confirm=1, plan_ok=False)
    d = ctl.tick(COLD, now=0.0)
    assert d.outcome == "held" and d.reason == "floor"
    assert (d.frm, d.to) == (2, 2) and state["size"] == 2
    assert "outcome=held" in d.line()
    # held arms the cooldown like any rendered decision
    assert ctl.tick(COLD, now=1.0) is None


def test_crashing_planner_renders_held_not_raise():
    def bad_plan(direction, frm, to):
        raise RuntimeError("boom")
    ctl = CapacityController(CapacityPolicy(confirm=1, max_size=3),
                             mode="auto", size_fn=lambda: 1,
                             plan_fn=bad_plan, apply_fn=lambda p: None)
    d = ctl.tick(HOT, now=0.0)
    assert d.outcome == "held" and "boom" in d.reason


def test_failing_apply_renders_held():
    def bad_apply(plan):
        raise RuntimeError("spawn refused")
    ctl = CapacityController(CapacityPolicy(confirm=1, max_size=3),
                             mode="auto", size_fn=lambda: 1,
                             plan_fn=lambda d, f, t: {"ok": True, "to": t},
                             apply_fn=bad_apply)
    d = ctl.tick(HOT, now=0.0)
    assert d.outcome == "held" and "spawn refused" in d.reason


# ---------------------------------------------------------------------------
# advise mode: the A/B control arm
# ---------------------------------------------------------------------------

def test_advise_logs_without_acting():
    ctl, state = _ctl(mode="advise", size=2, confirm=2)
    fired, t = _drive(ctl, HOT, 2)
    assert [d.outcome for d in fired] == ["advised"]
    assert state["size"] == 2 and state["applied"] == []
    # advise arms cooldown + flap state exactly like auto (A/B parity)
    assert ctl.tick(HOT, now=t) is None
    assert ctl.tick(COLD, now=t + 100.0) is None        # streak 1 of 2
    d = ctl.tick(COLD, now=t + 101.0)
    assert d is not None and d.outcome == "advised"
    assert ctl.flap_factor == 2.0                        # reversal tracked


# ---------------------------------------------------------------------------
# a whole ramp, a whole steady run: the counts a fleet run is held to
# ---------------------------------------------------------------------------

def _ramp_signals(n_windows, per_replica=4.0):
    """A window's signals under loadgen's `ramp:2:10` load: the queue an
    offered rate leaves at ONE replica's capacity (the controller divides
    by its size), so the rise wants capacity and the fall gives it back."""
    from tools.loadgen import parse_ramp_spec, ramp_rate
    ramp = parse_ramp_spec("ramp:2:10")
    for i in range(n_windows):
        rate = ramp_rate(float(i), float(n_windows), ramp)
        yield {"queue_depth": max(0.0, (rate - per_replica) * 4.0),
               "brownout_level": 0, "burn_rate": 0.0}


@pytest.mark.parametrize("mode", ["auto", "advise"])
def test_ramp_scales_up_under_load_and_drains_to_the_floor(mode):
    """One seeded ramp through either arm of the A/B: the closed loop
    leaves the floor WHILE the ramp still offers load and is back on it
    at the end, with two to four applied moves (a ceiling of 3), none
    held and at most the one damped reversal at the ramp's turn: the
    count does not explode. The advisory arm logs its decisions and moves
    nothing."""
    n = 60
    ctl, state = _ctl(size=1, mode=mode, confirm=2, cooldown_s=3.0,
                      queue_high=8.0, queue_low=1.0)
    sizes, first_up = [], None
    for i, sig in enumerate(_ramp_signals(n)):
        d = ctl.tick(sig, now=float(i))
        if d is not None and d.direction == "up" and first_up is None:
            first_up = i
        sizes.append(state["size"])
    # calm windows after the load has gone: the drain's last step
    for t in range(n, n + 30):
        if state["size"] == 1:
            break
        ctl.tick(COLD, now=float(t))
    snap = ctl.snapshot()
    assert first_up is not None and first_up < n * 2 // 3
    assert snap["ticks"] >= n
    assert snap["decisions"]["held"] == 0
    assert snap["decisions"]["flap_damped"] <= 1
    if mode == "auto":
        assert max(sizes) > 1 and state["size"] == 1    # up, then drained
        assert 2 <= snap["decisions"]["applied"] <= 4
        assert snap["decisions"]["advised"] == 0
    else:
        assert set(sizes) == {1} and state["applied"] == []
        assert snap["decisions"]["advised"] >= 1
        assert snap["decisions"]["applied"] == 0


@pytest.mark.parametrize("size,signals", [(1, NEUTRAL), (2, NEUTRAL),
                                          (1, COLD), (3, HOT)],
                         ids=["floor-comfortable", "above-floor-comfortable",
                              "floor-idle", "ceiling-hot"])
def test_steady_run_ticks_and_decides_nothing(size, signals):
    """The control run: under flat load the governor ticks every window
    and renders no decision of ANY outcome: zero flaps on a clean fleet."""
    ctl, state = _ctl(size=size, confirm=1, cooldown_s=0.0)
    runner = AutoscaleRunner(ctl, signals_fn=lambda: signals,
                             interval_s=0.01, emit=lambda line: None)
    assert [runner.tick_once() for _ in range(40)] == [None] * 40
    snap = ctl.snapshot()
    assert snap["ticks"] == 40 and state["size"] == size
    assert sum(snap["decisions"].values()) == 0 and state["applied"] == []


# ---------------------------------------------------------------------------
# snapshot + fleet mining + runner
# ---------------------------------------------------------------------------

def test_snapshot_shape():
    ctl, _ = _ctl(confirm=1)
    ctl.tick(HOT, now=0.0)
    snap = ctl.snapshot()
    assert snap["mode"] == "auto" and snap["size"] == 2
    assert snap["min"] == 1 and snap["max"] == 3
    assert snap["decisions"]["applied"] == 1
    assert snap["last"]["direction"] == "up"
    assert snap["cooldown_factor"] == 1.0


def test_signals_from_fleet_mines_worst_burn():
    fleet = {"queue_depth": 7.0, "brownout_level": 2,
             "slo": {"burn_rate": {"interactive": {"short": 3.0,
                                                   "long": 0.1},
                                   "batch": {"short": 0.5}}}}
    sig = signals_from_fleet(fleet, size=2)
    assert sig == {"queue_depth": 7.0, "brownout_level": 2,
                   "burn_rate": 3.0, "size": 2}
    # missing blocks degrade to calm, not KeyError
    assert signals_from_fleet({}, size=1)["burn_rate"] == 0.0


def test_runner_emits_decision_lines():
    ctl, state = _ctl(confirm=1)
    lines = []
    runner = AutoscaleRunner(ctl, signals_fn=lambda: HOT,
                             interval_s=0.01, emit=lines.append)
    d = runner.tick_once()
    assert d is not None and state["size"] == 2
    assert lines and lines[0].startswith("autoscale_decision direction=up")
    # a crashing signals_fn is a skipped window, not a crash
    runner._signals_fn = lambda: (_ for _ in ()).throw(OSError("down"))
    assert runner.tick_once() is None
    with pytest.raises(ValueError):
        AutoscaleRunner(ctl, signals_fn=dict, interval_s=0.0)


# ---------------------------------------------------------------------------
# trace_report autoscale section (telemetry/report.py)
# ---------------------------------------------------------------------------

def test_report_autoscale_section():
    from pipeedge_tpu.telemetry import report
    t = 1_000_000
    mk = lambda name, t0, t1: {"cat": "autoscale", "name": name,  # noqa: E731
                               "rank": 0, "stage": None, "mb": None,
                               "t0": t0, "t1": t1}
    spans = [
        mk("plan:up", t, t + 2_000_000),
        mk("apply:up", t + 2_000_000, t + 9_000_000),
        mk("plan:down", t + 20, t + 25),
        mk("held:down", t + 25, t + 25),
        mk("flap_damped:down", t + 30, t + 30),
        {"cat": "compute", "name": "stage0", "rank": 0, "stage": 0,
         "mb": 0, "t0": t, "t1": t + 10_000_000},
    ]
    rec = report.analyze_spans(spans, span_cost_ns=100.0)
    a = rec["autoscale"]
    assert a["plans"] == 2 and a["applies"] == 1
    assert a["held"] == 1 and a["flap_damped"] == 1
    assert a["by_direction"]["up"] == {"apply": 1, "plan": 1}
    assert a["by_direction"]["down"] == {"flap_damped": 1, "held": 1,
                                         "plan": 1}
    assert a["apply_ms"]["n"] == 1 and a["apply_ms"]["max"] == 7.0


def test_report_no_autoscale_section_on_plain_trace():
    from pipeedge_tpu.telemetry import report
    t = 1_000_000
    spans = [{"cat": "compute", "name": "stage0", "rank": 0, "stage": 0,
              "mb": 0, "t0": t, "t1": t + 10}]
    rec = report.analyze_spans(spans, span_cost_ns=100.0)
    assert rec["autoscale"] == {}
