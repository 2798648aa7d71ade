"""Committed real-chip (TPU v5e) profiles drive the native scheduler:
profile -> models.yml/device_types.yml -> sched-pipeline DP partition,
no TPU needed at test time.

Skipped until profiles/tpu/*.yml are generated on the chip
(profiles/README.md recipe), and skipped wherever the native
`sched-pipeline` binary can neither be found nor built — CI runners
without cmake/ninja would otherwise fail here on every run
(tests/README_TESTS.md documents the native build).
"""
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROF = os.path.join(REPO, "profiles", "tpu")
FILES = {name: os.path.join(PROF, name)
         for name in ("models.yml", "device_types.yml", "devices.yml")}


def _native_binary_present() -> bool:
    """The prebuilt binary exists, or the toolchain to build it does
    (build_native needs BOTH cmake and ninja — native/CMakeLists.txt
    is generated with -G Ninja)."""
    if os.path.exists(os.path.join(REPO, "native", "build",
                                   "sched-pipeline")):
        return True
    return bool(shutil.which("cmake") and shutil.which("ninja"))


pytestmark = [
    pytest.mark.skipif(
        not all(os.path.exists(p) for p in FILES.values()),
        reason="TPU profile fixtures not generated yet "
               "(profiles/README.md)"),
    pytest.mark.skipif(
        not _native_binary_present(),
        reason="native sched-pipeline binary unbuilt and no cmake+ninja "
               "toolchain to build it (tests/README_TESTS.md)"),
]


@pytest.fixture(scope="module")
def native_sched():
    from pipeedge_tpu.sched import scheduler
    scheduler.build_native()
    return scheduler


@pytest.mark.parametrize("model,layers", [
    ("google/vit-base-patch16-224", 48),
    ("google/vit-large-patch16-224", 96),
    ("bert-base-uncased", 48),
    ("facebook/deit-base-distilled-patch16-224", 48),
    ("gpt2", 48),
])
def test_sched_pipeline_on_tpu_profiles(native_sched, model, layers):
    """The DP scheduler produces a full-coverage 4-stage partition over four
    identical tpu-v5e devices from the committed chip profiles."""
    sched = native_sched.sched_pipeline(
        model, 2, 2, 8, dtype="bfloat16",
        models_file=FILES["models.yml"],
        dev_types_file=FILES["device_types.yml"],
        dev_file=FILES["devices.yml"])
    assert sched, "no viable schedule from the chip profiles"
    covered = []
    hosts = []
    for stage in sched:
        for host, (l, r) in ((h, tuple(v)) for h, v in stage.items()):
            covered.extend(range(l, r + 1))
            hosts.append(host)
    assert covered == list(range(1, layers + 1))
    assert len(hosts) == len(set(hosts))  # one stage per device
    # identical devices + negligible comm time at 100 Gbps -> the
    # throughput-optimal partition uses all four devices, roughly balanced
    assert len(sched) == 4, sched
    sizes = [len(range(tuple(v)[0], tuple(v)[1] + 1))
             for stage in sched for v in stage.values()]
    assert max(sizes) - min(sizes) <= layers // 4, sizes
