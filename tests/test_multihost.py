"""Multi-host SPMD smoke: the spmd driver composes with process-spanning
meshes via `MultiHostContext` (jax.distributed.initialize) — the mechanism
that joins TPU slices over DCN into one global device mesh (SURVEY.md §5.8,
the role of the reference's init_process_group bring-up, p2p:62)."""
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


import pytest

pytestmark = pytest.mark.fleet  # every test here spawns OS processes


def _cpu_multiprocess_collectives_available() -> bool:
    """Whether this jax build can run cross-process collectives on the
    CPU backend. jax 0.4.x's CPU client has no multiprocess collective
    implementation (no Gloo/MPI wiring in jaxlib <= 0.4.36): any
    computation spanning processes — including the jitted psum inside
    `multihost_utils.broadcast_one_to_all`, which `device_put` onto a
    process-spanning NamedSharding triggers via assert_equal — dies
    with `XlaRuntimeError: INVALID_ARGUMENT: Multiprocess computations
    aren't implemented on the CPU backend.` jax >= 0.5 ships a
    CpuCollectives/Gloo layer; on such a build this test must run (and
    the xfail below turns into a hard failure via strict=True +
    condition)."""
    import jax
    major, minor = (int(v) for v in jax.__version__.split(".")[:2])
    return (major, minor) >= (0, 5)


@pytest.mark.xfail(
    condition=not _cpu_multiprocess_collectives_available(),
    reason="jax 0.4.x CPU backend cannot run multiprocess collectives "
           "(XlaRuntimeError 'Multiprocess computations aren't "
           "implemented on the CPU backend' from the broadcast inside "
           "device_put-to-global-mesh); needs jax >= 0.5's Gloo CPU "
           "collectives or a real TPU fleet",
    strict=True, run=True)
def test_two_process_spmd_pipeline(tmp_path):
    with socket.create_server(("127.0.0.1", 0)) as s:
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    script = os.path.join(REPO, "tests", "multihost_spmd_main.py")
    # a clean environment: the parent test process forced its own platform
    # config, but each child must bring up its own 4-device CPU backend
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = REPO
    # each rank's log goes to a file: the ranks are waited for in turn, and
    # a pipe nobody reads yet holds 64 KB before its writer blocks
    logs = [open(tmp_path / f"rank{r}.log", "w+") for r in range(2)]
    procs = [subprocess.Popen([sys.executable, script, str(r), "2", coord],
                              env=env, stdout=log,
                              stderr=subprocess.STDOUT, text=True)
             for r, log in enumerate(logs)]
    outs = []
    try:
        for p in procs:
            p.wait(timeout=240)
    finally:
        for p, log in zip(procs, logs):
            p.kill()
            p.wait()
            log.seek(0)
            outs.append(log.read())
            log.close()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r}:\n{out}"
        assert f"MULTIHOST-OK rank={r} local=4 global=8" in out, out

    # the training leg: every rank saw the same descending loss sequence
    # (one global program; the ranks hold shards of one model) ...
    import re
    seqs = [re.search(r"train_losses=\[([^\]]+)\]", out).group(1)
            for out in outs]
    assert seqs[0] == seqs[1], seqs
    losses = [float(v) for v in seqs[0].split(",")]
    assert losses[-1] < losses[0], losses

    # ... and it matches a SINGLE-process oracle on this test's own
    # 8-device CPU backend, step for step: spanning the mesh over two
    # OS processes changed nothing about the training math
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pipeedge_tpu.models import ShardConfig
    from pipeedge_tpu.models import vit as vit_mod
    from pipeedge_tpu.models.layers import TransformerConfig
    from pipeedge_tpu.parallel import spmd
    from pipeedge_tpu.parallel import train as train_mod
    dp, n_stages = 2, 4
    cfg = TransformerConfig(model_type="vit", hidden_size=32,
                            num_hidden_layers=n_stages,
                            num_attention_heads=4, intermediate_size=64,
                            num_labels=5, image_size=16, patch_size=4)
    total = 4 * cfg.num_hidden_layers
    partition = [(4 * i + 1, 4 * (i + 1)) for i in range(n_stages)]
    stage_params = [vit_mod.init_params(
        cfg, ShardConfig(l, r, is_first=l == 1, is_last=r == total),
        seed=0) for l, r in partition]
    mesh = spmd.make_pipeline_mesh(n_stages, dp=dp)
    pipe = spmd.build_spmd_pipeline(vit_mod.FAMILY, cfg, partition,
                                    stage_params, mesh)
    batch = 2 * dp
    t_inputs = jnp.asarray(np.random.default_rng(7).normal(
        size=(n_stages + 1, batch, 3, 16, 16)), jnp.float32)
    t_labels = jnp.asarray(np.random.default_rng(8).integers(
        0, cfg.num_labels, size=(n_stages + 1, batch)), jnp.int32)
    step_fn, opt_state = train_mod.make_train_step(
        pipe, optax.sgd(0.05), t_inputs)
    params = pipe.params
    for want in losses:
        params, opt_state, loss = step_fn(params, opt_state, t_inputs,
                                          t_labels)
        np.testing.assert_allclose(float(loss), want, rtol=1e-4)
