"""Generate the committed HF-torch parity fixtures (one per model family).

Real-weights accuracy: pretrained
checkpoints are not downloadable in this zero-egress environment
(docs/REAL_WEIGHTS.md logs the attempt), so these fixtures anchor the
parity claim per family instead: HF torch's own float32 logits on a fixed
input, with weights built by the same seeded recipe `save_model_weights.py
--random` uses (torch.manual_seed(0) + HF init). The committed artifacts
are small (logits + a weight probe, not the weights); the anchor tests
(tests/test_weights_parity.py) regenerate the weights from the seed
recipe, run them through THIS framework's npz conversion + shard pipeline,
and must reproduce torch's recorded logits — catching drift in either the
HF init recipe (weight_probe check) or this framework's conversion/forward
for EVERY family, not just ViT. Reference capability anchored: per-model
weight loading (reference vit.py:121-159, bert.py:164-219, deit.py:131-156,
and the gpt2/llama families beyond it).

The moment real weights are obtainable, the identical path yields label
accuracy: swap --random for the pretrained fetch, keep everything else.

Usage: python tools/make_parity_fixture.py [model ...]   (default: all)
Writes tests/fixtures/<slug>_random_torch_logits.npz.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

INPUT_SEED = 1234
_FIXDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures")

# One anchor per model family. probe_keys: state-dict slices recorded so a
# failing test can distinguish "HF init recipe drifted" from "this
# framework's conversion/forward drifted". logits_attr: which HF output
# carries the reference-parity logits (DeiT: the reference classifier is
# the CLS head only, reference deit.py:224-227). tail_positions bounds the
# committed artifact for big-vocab causal models (last positions only).
SPECS = {
    "google/vit-base-patch16-224": dict(
        slug="vitb", kind="image", logits_attr="logits",
        probe_keys=["vit.encoder.layer.0.attention.attention.query.weight",
                    "classifier.weight"]),
    "facebook/deit-base-distilled-patch16-224": dict(
        slug="deitb", kind="image", logits_attr="cls_logits",
        probe_keys=["deit.encoder.layer.0.attention.attention.query.weight",
                    "cls_classifier.weight"]),
    "textattack/bert-base-uncased-CoLA": dict(
        slug="bert_cola", kind="ids", seq=32, logits_attr="logits",
        probe_keys=["bert.encoder.layer.0.attention.self.query.weight",
                    "classifier.weight"]),
    "gpt2": dict(
        slug="gpt2", kind="ids", seq=16, logits_attr="logits",
        tail_positions=2,
        probe_keys=["transformer.h.0.attn.c_attn.weight", "lm_head.weight"]),
    "pipeedge/test-tiny-llama": dict(
        slug="tiny_llama", kind="ids", seq=16, logits_attr="logits",
        probe_keys=["model.layers.0.self_attn.q_proj.weight",
                    "lm_head.weight"]),
}
# Back-compat aliases (round-2 single-model tool API)
MODEL = "google/vit-base-patch16-224"
FIXTURE = os.path.join(_FIXDIR, "vitb_random_torch_logits.npz")


def fixture_path(model_name: str) -> str:
    return os.path.join(_FIXDIR,
                        f"{SPECS[model_name]['slug']}_random_torch_logits.npz")


def build_torch_model(model_name: str = MODEL):
    from save_model_weights import _hf_model

    from pipeedge_tpu.models import registry
    cfg = registry.get_model_entry(model_name).config
    model = _hf_model(model_name, cfg, random_init=True)  # torch.manual_seed(0)
    return model.eval(), cfg


def fixture_input(cfg, model_name: str = MODEL) -> np.ndarray:
    """The fixed fixture input: seeded image batch or token ids."""
    spec = SPECS[model_name]
    rng = np.random.default_rng(INPUT_SEED)
    if spec["kind"] == "image":
        return rng.normal(size=(2, cfg.num_channels, cfg.image_size,
                                cfg.image_size)).astype(np.float32)
    return rng.integers(0, cfg.vocab_size,
                        size=(2, spec["seq"])).astype(np.int64)


def weight_probe(model, model_name: str) -> np.ndarray:
    sd = model.state_dict()
    return np.concatenate([
        sd[key].numpy().ravel()[:64] for key in SPECS[model_name]["probe_keys"]
    ]).astype(np.float32)


def make_fixture(model_name: str) -> str:
    import torch
    spec = SPECS[model_name]
    model, cfg = build_torch_model(model_name)
    x = fixture_input(cfg, model_name)
    with torch.no_grad():
        out = model(torch.from_numpy(x))
    logits = getattr(out, spec["logits_attr"]).numpy()
    tail = spec.get("tail_positions")
    if tail:
        logits = logits[:, -tail:]
    path = fixture_path(model_name)
    os.makedirs(_FIXDIR, exist_ok=True)
    np.savez(path, logits=logits, input_seed=INPUT_SEED,
             weight_probe=weight_probe(model, model_name))
    print(f"wrote {path}: logits {logits.shape}")
    return path


def main():
    names = sys.argv[1:] or list(SPECS)
    for name in names:
        make_fixture(name)


if __name__ == "__main__":
    main()
