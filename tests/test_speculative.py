"""Speculative decoding: greedy-exact vs the plain pipeline.

The guarantee under test (parallel/speculative.py): for fp caches,
SpeculativeDecoder.generate is token-identical to the target pipeline's
own greedy generate, for ANY draft over the same vocabulary — acceptance
only changes the dispatch count. Drafts here are independently-seeded
(gpt2) or noise-perturbed (llama/mistral) models, so rounds exercise
both accepted and rejected prefixes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pipeedge_tpu.models import registry
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.parallel.speculative import SpeculativeDecoder

pytestmark = pytest.mark.slow   # compile-heavy decode programs

MAX_LEN = 48


def _pipe(name, partition=None, seed_perturb=None, max_len=MAX_LEN,
          **kw):
    cfg = registry.get_model_config(name)
    total = registry.get_model_layers(name)
    partition = partition or [(1, total)]
    family = registry.get_model_entry(name).family.FAMILY
    params = []
    for i, (l, r) in enumerate(partition):
        _, p, _ = registry.module_shard_factory(name, None, l, r,
                                                unroll=False)
        if seed_perturb is not None:
            rng = np.random.default_rng(seed_perturb + i)
            p = jax.tree_util.tree_map(
                lambda x: x + jnp.asarray(
                    rng.normal(scale=0.02, size=x.shape), x.dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
        params.append(p)
    return decode.DecodePipeline(family, cfg, partition, params,
                                 max_len=max_len, **kw)


@pytest.fixture(scope="module")
def gpt2_pipes():
    return _pipe("pipeedge/test-tiny-gpt2"), \
        _pipe("pipeedge/test-tiny-gpt2", seed_perturb=11)


def _ids(batch, prompt_len, vocab=100, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(batch, prompt_len))


@pytest.mark.parametrize("gamma", [1, 2, 4])
@pytest.mark.parametrize("batch", [1, 3])
def test_spec_greedy_exact_gpt2(gpt2_pipes, gamma, batch):
    target, draft = gpt2_pipes
    ids = _ids(batch, 8)
    want = np.asarray(target.generate(ids, 12))
    spec = SpeculativeDecoder(target, draft, gamma=gamma)
    got = np.asarray(spec.generate(ids, 12))
    np.testing.assert_array_equal(got, want)
    assert 0.0 <= spec.last_acceptance_rate <= 1.0


@pytest.mark.parametrize("sync", ["host", "device"])
def test_spec_rejected_round_outlives_donated_steps(gpt2_pipes, sync):
    """Both pipelines donate their caches to every span and step: after a
    round that rejects, the target's cache holds rows past the accepted
    ones and the draft's is rewound by position only. Tokens stay the
    target's own, in both sync modes."""
    target, draft = gpt2_pipes
    ids = _ids(2, 8, seed=3)
    want = np.asarray(target.generate(ids, 14))
    spec = SpeculativeDecoder(target, draft, gamma=4, sync=sync)
    got = np.asarray(spec.generate(ids, 14))
    np.testing.assert_array_equal(got, want)
    assert spec.last_acceptance_rate < 1.0      # some round rejected


def test_spec_self_draft_accepts_everything(gpt2_pipes):
    """Draft == target: every proposal matches, acceptance 1.0, each
    round commits gamma+1 tokens."""
    target, _ = gpt2_pipes
    ids = _ids(2, 8)
    want = np.asarray(target.generate(ids, 10))
    spec = SpeculativeDecoder(target, target, gamma=3)
    got = np.asarray(spec.generate(ids, 10))
    np.testing.assert_array_equal(got, want)
    assert spec.last_acceptance_rate == 1.0


def test_spec_multistage_target(gpt2_pipes):
    """The verify span rides the pipeline stages like any decode."""
    _, draft = gpt2_pipes
    target = _pipe("pipeedge/test-tiny-gpt2", partition=[(1, 4), (5, 8)])
    ids = _ids(2, 8)
    want = np.asarray(target.generate(ids, 12))
    got = np.asarray(
        SpeculativeDecoder(target, draft, gamma=3).generate(ids, 12))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["pipeedge/test-tiny-llama",
                                  "pipeedge/test-tiny-mistral"])
def test_spec_greedy_exact_llama_family(name):
    """RoPE/GQA (and mistral's sliding window) under span verification."""
    target = _pipe(name)
    draft = _pipe(name, seed_perturb=23)
    ids = _ids(2, 8)
    want = np.asarray(target.generate(ids, 12))
    spec = SpeculativeDecoder(target, draft, gamma=3)
    got = np.asarray(spec.generate(ids, 12))
    np.testing.assert_array_equal(got, want)


def test_extend_matches_serial_steps(gpt2_pipes):
    """The verify primitive itself: one K-token extend produces the same
    last-stage logits and cache state as K serial decode steps."""
    target, _ = gpt2_pipes
    ids = jnp.asarray(_ids(2, 8), jnp.int32)
    k_span = 4
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 100, size=(2, k_span)), jnp.int32)

    _, caches_a = target._prefill(ids)
    span_logits, caches_a = target.extend(toks, caches_a, 8)

    _, caches_b = target._prefill(ids)
    serial = []
    for j in range(k_span):
        data = toks[:, j:j + 1]
        for i, st in enumerate(target.stages):
            data, caches_b[i] = target._decode_step(st, data, caches_b[i],
                                                    8 + j)
        serial.append(data[:, 0])
    np.testing.assert_allclose(np.asarray(span_logits),
                               np.asarray(jnp.stack(serial, axis=1)),
                               rtol=2e-5, atol=2e-5)
    for ca, cb in zip(caches_a, caches_b):
        for key in ca:
            np.testing.assert_allclose(np.asarray(ca[key][:, :, :12]),
                                       np.asarray(cb[key][:, :, :12]),
                                       rtol=2e-5, atol=2e-5)


def test_spec_moe_dropless_ok_capacity_refused():
    """Dropless MoE keeps the greedy-exact guarantee; capacity-bounded
    routing is refused with the reason."""
    target = _pipe("pipeedge/test-tiny-moe")
    draft = _pipe("pipeedge/test-tiny-moe", seed_perturb=5)
    ids = _ids(2, 8)
    want = np.asarray(target.generate(ids, 10))
    got = np.asarray(
        SpeculativeDecoder(target, draft, gamma=2).generate(ids, 10))
    np.testing.assert_array_equal(got, want)

    import dataclasses
    target.cfg = dataclasses.replace(
        registry.get_model_config("pipeedge/test-tiny-moe"),
        capacity_factor=1.0)
    with pytest.raises(ValueError, match="capacity-bounded"):
        SpeculativeDecoder(target, draft, gamma=2)


def test_spec_vocab_mismatch_refused(gpt2_pipes):
    import dataclasses
    target, draft = gpt2_pipes
    odd = _pipe("pipeedge/test-tiny-gpt2")
    odd.cfg = dataclasses.replace(odd.cfg, vocab_size=101)
    with pytest.raises(ValueError, match="vocabulary"):
        SpeculativeDecoder(target, odd)


def test_spec_tp_target():
    """A tensor-parallel target (head-sharded cache under shard_map)
    verifies spans like the plain pipeline: spec == plain greedy."""
    from jax.sharding import Mesh
    target_plain = _pipe("pipeedge/test-tiny-gpt2")
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    target_tp = _pipe("pipeedge/test-tiny-gpt2", mesh=mesh)
    draft = _pipe("pipeedge/test-tiny-gpt2", seed_perturb=11)
    ids = _ids(2, 8)
    want = np.asarray(target_plain.generate(ids, 12))
    got = np.asarray(
        SpeculativeDecoder(target_tp, draft, gamma=3).generate(ids, 12))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["pipeedge/test-tiny-gpt2",
                                  "pipeedge/test-tiny-llama",
                                  "pipeedge/test-tiny-mistral"])
def test_prefix_cache_matches_full_prefill(name):
    """Prompt caching: precompute_prefix + suffix-span generate ==
    monolithic-prompt generate, token for token (fp caches), for every
    decode family incl. RoPE at global offsets and sliding windows."""
    pipe = _pipe(name)
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, 100, size=(1, 6))
    suffix = rng.integers(0, 100, size=(3, 5))
    full = np.concatenate([np.repeat(prefix, 3, axis=0), suffix], axis=1)
    want = np.asarray(pipe.generate(full, 10))
    handle = pipe.precompute_prefix(prefix)
    got = np.asarray(pipe.generate(suffix, 10, prefix=handle))
    # the returned array omits the prefix; compare suffix + continuation
    np.testing.assert_array_equal(got, want[:, 6:])
    # the handle is reusable (a second batch, sampled decode)
    suffix2 = rng.integers(0, 100, size=(2, 5))
    full2 = np.concatenate([np.repeat(prefix, 2, axis=0), suffix2], axis=1)
    want2 = np.asarray(pipe.generate(full2, 8, temperature=0.8, seed=3))
    got2 = np.asarray(pipe.generate(suffix2, 8, temperature=0.8, seed=3,
                                    prefix=handle))
    np.testing.assert_array_equal(got2, want2[:, 6:])


def test_prefix_cache_multistage():
    """Prefix reuse rides multi-stage pipelines: a prefix-seeded request
    matches the full-prompt run under a multi-stage partition."""
    target = _pipe("pipeedge/test-tiny-gpt2", partition=[(1, 4), (5, 8)])
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, 100, size=(1, 4))
    suffix = rng.integers(0, 100, size=(2, 4))
    full = np.concatenate([np.repeat(prefix, 2, axis=0), suffix], axis=1)
    want = np.asarray(target.generate(full, 8))
    got = np.asarray(target.generate(
        suffix, 8, prefix=target.precompute_prefix(prefix)))
    np.testing.assert_array_equal(got, want[:, 4:])


@pytest.mark.parametrize("name", ["pipeedge/test-tiny-gpt2",
                                  "pipeedge/test-tiny-llama"])
def test_spec_with_prefix_cache(name):
    """Speculative decoding composes with prompt caching: both pipelines
    seed from the shared prefix (each with its own K/V), the first draft
    catch-up span covers the whole suffix, and the output still equals
    the target's plain full-prompt greedy decode."""
    target = _pipe(name)
    draft = _pipe(name, seed_perturb=31)
    spec = SpeculativeDecoder(target, draft, gamma=3)
    rng = np.random.default_rng(41)
    prefix = rng.integers(0, 100, size=(1, 6))
    suffix = rng.integers(0, 100, size=(2, 4))
    full = np.concatenate([np.repeat(prefix, 2, axis=0), suffix], axis=1)
    want = np.asarray(target.generate(full, 11))
    handle = spec.precompute_prefix(prefix)
    got = np.asarray(spec.generate(suffix, 11, prefix=handle))
    np.testing.assert_array_equal(got, want[:, 6:])
    # full acceptance path too (self-draft) with the same handle shape
    spec2 = SpeculativeDecoder(target, target, gamma=2)
    got2 = np.asarray(spec2.generate(
        suffix, 11, prefix=spec2.precompute_prefix(prefix)))
    np.testing.assert_array_equal(got2, want[:, 6:])
    assert spec2.last_acceptance_rate == 1.0


@pytest.mark.parametrize("gamma", [2, 4])
def test_device_rounds_token_identical_two_syncs_per_round(gpt2_pipes,
                                                           gamma):
    """sync='device' (the default here via 'auto') fuses each round's
    DRAFT side into one program: tokens identical to sync='host' (the
    target verify runs the same compiled stage programs in both modes),
    and the host round trips drop from (gamma+1)/round to 2/round (one
    packed proposal readback + the verify argmax)."""
    target, draft = gpt2_pipes
    ids = _ids(2, 8, seed=5)
    host = SpeculativeDecoder(target, draft, gamma=gamma, sync="host")
    dev = SpeculativeDecoder(target, draft, gamma=gamma, sync="device")
    want = np.asarray(host.generate(ids, 12))
    got = np.asarray(dev.generate(ids, 12))
    np.testing.assert_array_equal(got, want)
    assert dev.last_acceptance_rate == host.last_acceptance_rate
    # host pays 1 + rounds*(gamma+1); device pays 1 + 2*rounds
    n_rounds = (host.last_sync_count - 1) // (gamma + 1)
    assert host.last_sync_count == 1 + n_rounds * (gamma + 1)
    assert dev.last_sync_count == 1 + 2 * n_rounds
    assert dev.last_sync_count < host.last_sync_count


def test_device_rounds_with_prefix_and_auto_fallback(gpt2_pipes):
    """Device rounds compose with prompt caching (the catch-up span is
    just longer on round 1); 'auto' falls back to host rounds when a
    pipeline pins stages to devices, and sync='device' refuses with the
    reason."""
    target, draft = gpt2_pipes
    rng = np.random.default_rng(77)
    prefix = rng.integers(0, 100, size=(1, 6))
    suffix = rng.integers(0, 100, size=(2, 4))
    spec = SpeculativeDecoder(target, draft, gamma=3)
    assert spec.sync == "device"     # auto picked the fused rounds
    handle = spec.precompute_prefix(prefix)
    got = np.asarray(spec.generate(suffix, 9, prefix=handle))
    want = np.asarray(
        SpeculativeDecoder(target, draft, gamma=3, sync="host")
        .generate(suffix, 9, prefix=handle))
    np.testing.assert_array_equal(got, want)

    placed = _pipe("pipeedge/test-tiny-gpt2",
                   devices=[jax.devices()[0]])
    # a placed TARGET is fine (its verify rides the normal stage
    # programs either way); a placed DRAFT forces the host fallback
    assert SpeculativeDecoder(placed, draft, gamma=2).sync == "device"
    auto = SpeculativeDecoder(target, placed, gamma=2)
    assert auto.sync == "host"       # fell back, still works
    with pytest.raises(ValueError, match="device placement"):
        SpeculativeDecoder(target, placed, gamma=2, sync="device")


def test_device_rounds_eligibility_gate():
    """The fused-round gate names every blocker: per-stage placement and
    tp/ep/tp x ep meshes all refuse (their programs carry shardings or
    host-driven transfers a single jitted round must not inline)."""
    from types import SimpleNamespace as NS

    from pipeedge_tpu.parallel.speculative import _device_rounds_eligible

    def pipe(**kw):
        base = dict(stages=[{"device": None}], mesh=None, ep_mesh=None,
                    tp_ep_mesh=None)
        base.update(kw)
        return NS(**base)

    assert _device_rounds_eligible(pipe()) is None
    assert "device placement" in _device_rounds_eligible(
        pipe(stages=[{"device": object()}]))
    assert "tensor-parallel" in _device_rounds_eligible(
        pipe(mesh=object()))
    assert "expert-parallel" in _device_rounds_eligible(
        pipe(ep_mesh=object()))
    assert "tp x ep" in _device_rounds_eligible(pipe(tp_ep_mesh=object()))
