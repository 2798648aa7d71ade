"""Real-sized parity anchors vs committed HF-torch logits (in-process).

Separate from test_weights.py because that module is fleet-marked
(subprocess CLIs); these tests are in-process, only slow (full-size
compiles + two forwards per family).

One anchor per model family: the torch side is FROZEN
at fixture-generation time (tools/make_parity_fixture.py), so HF init-
recipe drift and this framework's conversion/forward drift are both
caught for every family, not just ViT.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from pipeedge_tpu.models import registry


@pytest.mark.slow
@pytest.mark.parametrize("model_name", [
    "google/vit-base-patch16-224",
    "facebook/deit-base-distilled-patch16-224",
    "textattack/bert-base-uncased-CoLA",
    "gpt2",
    "pipeedge/test-tiny-llama",
])
def test_full_size_parity_vs_committed_torch_logits(model_name, tmp_path,
                                                    monkeypatch):
    """Parity ANCHOR: our npz conversion + shard pipeline must reproduce
    HF torch's own float32 logits recorded in the committed per-family
    fixture, regenerating the weights from the same seeded --random
    recipe. Pretrained weights are not downloadable here (zero egress,
    docs/REAL_WEIGHTS.md); with them, this same path yields accuracy."""
    import save_model_weights
    from tools.make_parity_fixture import (SPECS, build_torch_model,
                                           fixture_input, fixture_path,
                                           weight_probe)

    spec = SPECS[model_name]
    fx = np.load(fixture_path(model_name))
    monkeypatch.chdir(tmp_path)

    # regenerate the seeded weights; probe guards the HF init recipe
    model, cfg = build_torch_model(model_name)
    np.testing.assert_allclose(
        weight_probe(model, model_name), fx["weight_probe"], rtol=0, atol=0,
        err_msg=f"HF --random init recipe drifted for {model_name}; "
                "regenerate tools/make_parity_fixture.py")
    del model

    save_model_weights.save_weights(model_name, "w.npz", random_init=True)
    layers = registry.get_model_layers(model_name)
    fn, params, _ = registry.module_shard_factory(model_name, "w.npz", 1,
                                                  layers)
    x = jnp.asarray(fixture_input(cfg, model_name))
    got = np.asarray(fn(params, x))
    tail = spec.get("tail_positions")
    if tail:
        got = got[:, -tail:]
    np.testing.assert_allclose(got, fx["logits"], rtol=2e-4, atol=2e-4)
