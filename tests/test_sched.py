"""Native scheduler (sched-pipeline) golden tests.

The reference ships its DP scheduler untested (SURVEY.md §4); here the binary
is cross-checked against a brute-force enumerator over all feasible
contiguous partitions and device assignments, using the Python cost model
(which mirrors the native one — reference sched/__init__.py docstring).
"""
import itertools
import os
import shutil
import subprocess

import pytest
import yaml

from pipeedge_tpu import sched
from pipeedge_tpu.sched import yaml_files, yaml_types
from pipeedge_tpu.sched.scheduler import (_REPO_BUILD_PATHS, build_native,
                                          sched_pipeline)

BIN = _REPO_BUILD_PATHS[0]
pytestmark = pytest.mark.skipif(
    not (os.path.exists(BIN) or shutil.which('sched-pipeline')
         or shutil.which('cmake')),
    reason="sched-pipeline binary not built and no native toolchain")


@pytest.fixture(scope="module", autouse=True)
def _ensure_binary():
    """Build on demand at fixture time (not import time, so unrelated pytest
    collection never triggers a native compile)."""
    global BIN
    # build_native returns at once when the tree is fresh, and rebuilds a
    # tree left over from other sources
    built = build_native() or shutil.which('sched-pipeline')
    if built is None:
        pytest.skip("sched-pipeline auto-build failed")
    BIN = built

BATCH = 8
DTYPE = 'torch.float32'


def _write_files(tmp_path, models, device_types, devices):
    mf = tmp_path / "models.yml"
    tf = tmp_path / "device_types.yml"
    df = tmp_path / "devices.yml"
    yaml_files.yaml_save(models, str(mf))
    yaml_files.yaml_save(device_types, str(tf))
    yaml_files.yaml_save(devices, str(df))
    return str(mf), str(tf), str(df)


def _brute_force_bottleneck(model, device_types, devices, batch, dtype):
    """Enumerate every contiguous partition + device-instance assignment."""
    n_layers = model['layers']
    # expand device instances (type name repeated per host)
    instances = []
    for tname, hosts in devices.items():
        if tname in device_types:
            instances.extend([tname] * len(hosts))

    model_full = dict(model)
    # expand repeated blocks like the native loader (sched-pipeline.cpp:24-45)
    po = model['parameters_out']
    model_full['parameters_out'] = [po[i % len(po)] for i in range(n_layers)]

    def feasible(tname, l, r):  # 0-based inclusive
        need = sched.mem_bytes(model_full, l, r, dtype, batch)
        return device_types[tname]['mem_MB'] * 1024 * 1024 > need

    def comp(tname, l, r):
        prof = device_types[tname]['model_profiles']['m'][0]
        return sched.computation_time(prof, l, r)

    def comm(tname_u, tname_v, r):
        data = sched.ubatch_bytes(model_full['parameters_out'][r], batch, dtype)
        bw = min(device_types[tname_u]['bw_Mbps'], device_types[tname_v]['bw_Mbps'])
        return sched.communication_time_bw(bw, data)

    best = float('inf')
    for n_stages in range(1, len(instances) + 1):
        for cuts in itertools.combinations(range(1, n_layers), n_stages - 1):
            bounds = [0] + list(cuts) + [n_layers]
            ranges = [(bounds[i], bounds[i + 1] - 1) for i in range(n_stages)]
            for assign in itertools.permutations(instances, n_stages):
                ok = all(feasible(t, l, r) for t, (l, r) in zip(assign, ranges))
                if not ok:
                    continue
                cost = 0.0
                for k, (t, (l, r)) in enumerate(zip(assign, ranges)):
                    cost = max(cost, comp(t, l, r))
                    if k < n_stages - 1:
                        cost = max(cost, comm(t, assign[k + 1], r))
                best = min(best, cost)
    return best


def _sched_cost(schedule, model, device_types, devices, batch, dtype):
    """Bottleneck cost of a returned schedule."""
    host_type = {}
    for tname, hosts in devices.items():
        for h in hosts:
            host_type[h] = tname
    model_full = dict(model)
    po = model['parameters_out']
    model_full['parameters_out'] = [po[i % len(po)]
                                    for i in range(model['layers'])]
    cost = 0.0
    for k, stage in enumerate(schedule):
        (host, (l1, r1)), = stage.items()
        t = host_type[host]
        prof = device_types[t]['model_profiles']['m'][0]
        cost = max(cost, sched.computation_time(prof, l1 - 1, r1 - 1))
        if k < len(schedule) - 1:
            (host2, _), = schedule[k + 1].items()
            t2 = host_type[host2]
            data = sched.ubatch_bytes(model_full['parameters_out'][r1 - 1],
                                      batch, dtype)
            bw = min(device_types[t]['bw_Mbps'], device_types[t2]['bw_Mbps'])
            cost = max(cost, sched.communication_time_bw(bw, data))
    return cost


def _mk_model(n_layers, params_out, mem_mb, params_in=1000):
    return yaml_types.yaml_model(n_layers, params_in, params_out, mem_mb)


def _mk_type(mem_mb, bw, time_s):
    return yaml_types.yaml_device_type(
        mem_mb, bw, {'m': [yaml_types.yaml_model_profile(DTYPE, BATCH, time_s)]})


def test_optimal_heterogeneous(tmp_path):
    """Fast device should get more layers; result must match brute force."""
    n = 6
    models = {'m': _mk_model(n, [1000] * n, [1.0] * n)}
    device_types = {
        'fast': _mk_type(1024, 1000, [0.1] * n),
        'slow': _mk_type(1024, 1000, [0.3] * n),
    }
    devices = {'fast': ['f0'], 'slow': ['s0']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                              dev_types_file=tf, dev_file=df)
    got = _sched_cost(schedule, models['m'], device_types, devices, BATCH, DTYPE)
    want = _brute_force_bottleneck(models['m'], device_types, devices, BATCH, DTYPE)
    assert got == pytest.approx(want, rel=1e-9)
    # layers are contiguous and cover [1, n]
    covered = []
    for stage in schedule:
        (_, (l, r)), = stage.items()
        covered.extend(range(l, r + 1))
    assert covered == list(range(1, n + 1))


def test_memory_constraint_forces_split(tmp_path):
    """One device can't hold the model -> must split across two."""
    n = 4
    big_mem = 100.0  # MB per layer
    models = {'m': _mk_model(n, [1000] * n, [big_mem] * n)}
    device_types = {'small': _mk_type(250, 1000, [0.1] * n)}
    devices = {'small': ['h0', 'h1', 'h2']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                              dev_types_file=tf, dev_file=df)
    assert len(schedule) >= 2
    hosts = [list(s.keys())[0] for s in schedule]
    assert len(set(hosts)) == len(hosts)  # distinct hosts per stage
    want = _brute_force_bottleneck(models['m'], device_types, devices, BATCH, DTYPE)
    got = _sched_cost(schedule, models['m'], device_types, devices, BATCH, DTYPE)
    assert got == pytest.approx(want, rel=1e-9)


def test_infeasible_returns_empty(tmp_path):
    n = 2
    models = {'m': _mk_model(n, [1000] * n, [10000.0] * n)}
    device_types = {'tiny': _mk_type(1, 1000, [0.1] * n)}
    devices = {'tiny': ['h0']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                              dev_types_file=tf, dev_file=df)
    assert schedule == []


def test_repeated_blocks_and_wrapped_flow_lists(tmp_path):
    """parameters_out shorter than layers repeats (sched-pipeline.cpp:24-45);
    long PyYAML flow lists wrap across lines and must still parse."""
    n = 48
    models = {'m': _mk_model(n, [302592, 151296, 756480, 151296],
                             [25.0 + 0.001 * i for i in range(n)],
                             params_in=150528)}
    device_types = {'dev': _mk_type(4096, 1000,
                                    [0.05 + 0.0001 * i for i in range(n)])}
    devices = {'dev': ['h0', 'h1']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    # confirm the file really has wrapped flow lists
    with open(tf) as f:
        assert any(line.rstrip().endswith(',') for line in f)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                              dev_types_file=tf, dev_file=df)
    assert len(schedule) >= 1
    covered = []
    for stage in schedule:
        (_, (l, r)), = stage.items()
        covered.extend(range(l, r + 1))
    assert covered == list(range(1, n + 1))


def test_type_without_profile_skipped(tmp_path):
    n = 4
    models = {'m': _mk_model(n, [1000] * n, [1.0] * n)}
    device_types = {
        'good': _mk_type(1024, 1000, [0.1] * n),
        'noprof': yaml_types.yaml_device_type(99999, 99999, {}),
    }
    devices = {'good': ['g0'], 'noprof': ['n0']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                              dev_types_file=tf, dev_file=df)
    hosts = [list(s.keys())[0] for s in schedule]
    assert 'n0' not in hosts


def test_bfloat16_dtype_supported(tmp_path):
    """TPU extension: bf16 halves edge bytes and buffer memory."""
    n = 4
    models = {'m': _mk_model(n, [1000] * n, [1.0] * n)}
    device_types = {'dev': yaml_types.yaml_device_type(
        1024, 1000,
        {'m': [yaml_types.yaml_model_profile('bfloat16', BATCH, [0.1] * n)]})}
    devices = {'dev': ['h0']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    schedule = sched_pipeline('m', 2, 2, BATCH, dtype='bfloat16',
                              models_file=mf, dev_types_file=tf, dev_file=df)
    assert schedule == [{'h0': [1, n]}]


def test_dtype_name_normalization(tmp_path):
    """Profiles with bare jnp names match torch-style requests and vice
    versa (the TPU profiler writes 'float32', reference files
    'torch.float32')."""
    n = 4
    models = {'m': _mk_model(n, [1000] * n, [1.0] * n)}
    for prof_dtype, req_dtype in (('float32', 'torch.float32'),
                                  ('torch.float32', 'float32')):
        device_types = {'dev': yaml_types.yaml_device_type(
            1024, 1000,
            {'m': [yaml_types.yaml_model_profile(prof_dtype, BATCH,
                                                 [0.1] * n)]})}
        devices = {'dev': ['h0']}
        mf, tf, df = _write_files(tmp_path, models, device_types, devices)
        schedule = sched_pipeline('m', 2, 2, BATCH, dtype=req_dtype,
                                  models_file=mf, dev_types_file=tf,
                                  dev_file=df)
        assert schedule == [{'h0': [1, n]}], (prof_dtype, req_dtype)


def test_unknown_model_errors(tmp_path):
    models = {'m': _mk_model(2, [10, 10], [1.0, 1.0])}
    device_types = {'dev': _mk_type(1024, 1000, [0.1, 0.1])}
    devices = {'dev': ['h0']}
    mf, tf, df = _write_files(tmp_path, models, device_types, devices)
    with pytest.raises(subprocess.CalledProcessError):
        sched_pipeline('nope', 2, 2, BATCH, dtype=DTYPE, models_file=mf,
                       dev_types_file=tf, dev_file=df)


def test_cost_model_mem_bytes():
    model = {'layers': 3, 'parameters_in': 100,
             'parameters_out': [10, 20, 30], 'mem_MB': [1.0, 1.0, 1.0]}
    # first stage: no recv buffers; 2 send buffers + processing in+out
    got = sched.mem_bytes(model, 0, 1, DTYPE, 2)
    want = 2 * 1024 * 1024 + (20 * 2 * 4) * 2 + (100 * 2 * 4 + 20 * 2 * 4)
    assert got == want
    # middle stage: recv + send buffers
    got = sched.mem_bytes(model, 2, 2, DTYPE, 2)
    want = 1 * 1024 * 1024 + (20 * 2 * 4) * 2 + (30 * 2 * 4) * 2 \
        + (20 * 2 * 4 + 30 * 2 * 4)
    assert got == want


def test_profiles_upsert_semantics(tmp_path):
    """sched/profiles.py merge rules: dup model refuses without overwrite;
    device-type capacity must not silently change; (dtype, batch) keys a
    device type's model profiles with normalized dtype comparison."""
    import yaml as yaml_mod

    from pipeedge_tpu.sched import profiles

    results_yml = tmp_path / "r.yml"
    rec = {"model_name": "m", "dtype": "torch.float32", "batch_size": 2,
           "layers": 2,
           "profile_data": [
               {"layer": 1, "time": 0.1, "memory": 5.0,
                "shape_in": [[3, 4]], "shape_out": [[3, 4], [3, 4]]},
               {"layer": 2, "time": 0.2, "memory": 6.0,
                "shape_in": [[3, 4], [3, 4]], "shape_out": [[7]]},
           ]}
    with open(results_yml, "w") as f:
        yaml_mod.safe_dump(rec, f)
    res = profiles.ProfilerResults.load(str(results_yml))

    models_yml = str(tmp_path / "models.yml")
    profiles.upsert_model(models_yml, res)
    with pytest.raises(profiles.ProfileError, match="already exists"):
        profiles.upsert_model(models_yml, res)
    profiles.upsert_model(models_yml, res, overwrite=True)
    entry = yaml_mod.safe_load(open(models_yml))["m"]
    assert entry == {"layers": 2, "parameters_in": 12,
                     "parameters_out": [24, 7], "mem_MB": [5.0, 6.0]}

    types_yml = str(tmp_path / "types.yml")
    with pytest.raises(profiles.ProfileError, match="required"):
        profiles.upsert_device_type(types_yml, "dev", res)
    profiles.upsert_device_type(types_yml, "dev", res, mem_MB=100, bw_Mbps=10)
    with pytest.raises(profiles.ProfileError, match="mismatch"):
        profiles.upsert_device_type(types_yml, "dev", res, mem_MB=999,
                                    bw_Mbps=10)
    # same (dtype, batch) under a different spelling is the SAME key
    res2 = profiles.ProfilerResults(
        model_name="m", dtype="float32", batch_size=2, layers=2,
        profile_data=res.profile_data)
    with pytest.raises(profiles.ProfileError, match="already exists"):
        profiles.upsert_device_type(types_yml, "dev", res2)
    profiles.upsert_device_type(types_yml, "dev", res2, overwrite=True)
    # a different batch size appends a second profile
    res3 = profiles.ProfilerResults(
        model_name="m", dtype="float32", batch_size=8, layers=2,
        profile_data=res.profile_data)
    profiles.upsert_device_type(types_yml, "dev", res3)
    out = yaml_mod.safe_load(open(types_yml))["dev"]
    assert [p["batch_size"] for p in out["model_profiles"]["m"]] == [2, 8]

    # inconsistent record counts refuse at load
    bad = dict(rec, layers=3)
    with open(results_yml, "w") as f:
        yaml_mod.safe_dump(bad, f)
    with pytest.raises(profiles.ProfileError, match="layer count"):
        profiles.ProfilerResults.load(str(results_yml))


def test_build_tree_is_trusted_only_for_its_sources(monkeypatch, tmp_path):
    """native/build is git-ignored and outlives checkouts and copies: its
    binaries count as built only while the digest stamped beside them is
    that of the sources (a stale or unstamped tree is rebuilt, not run)."""
    from pipeedge_tpu.sched import scheduler
    assert scheduler._is_fresh(BIN)
    stamp = tmp_path / ".sources.sha256"
    monkeypatch.setattr(scheduler, "_SOURCES_STAMP", str(stamp))
    assert not scheduler._is_fresh(BIN)             # no stamp
    stamp.write_text("0" * 64)
    assert not scheduler._is_fresh(BIN)             # other sources
    stamp.write_text(scheduler._sources_digest())
    assert scheduler._is_fresh(BIN)
    assert not scheduler._is_fresh(str(tmp_path / "no-such-binary"))
