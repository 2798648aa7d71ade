"""Weights from `--seed`, written in the published checkpoint formats.

The program is given a weights file, as its users give it one, and the
plain references read the same file: neither sees the other's parameter
tree. The key schemes are the public ones (Google's ViT npz as the ViT paper's
checkpoints use it; the HF GPT2LMHeadModel state dict). Values are
float16 on disk, which holds every bfloat16 the program will round them to
with room to spare and halves the file.

Every tensor is random, biases and layer-norm parameters included, so that
a dropped bias or a swapped scale shows in the logits. `draw` below is a
`_Source`: a pool of seeded uniforms that each tensor reads from an offset
of its own."""
import os

import numpy as np

STD = 0.02
_HALF_WIDTH = STD * 3.0 ** 0.5      # a uniform of this half-width has that std
_POOL = 1 << 26                     # values drawn a run; more than any tensor
                                    # of the configurations here holds


class _Source:
    """Values for every tensor from one pool of uniforms drawn from the
    seed: mean 0, standard deviation 0.02 as the published initialisers
    have. Each tensor is the pool read from an offset of its own (drawn from
    the same seed), so tensors differ and a run draws 67 million numbers,
    not the 0.3 billion a model holds: every run of every cell pays for
    this, and neither speed nor the comparison with the reference depends
    on the weights being independent."""

    def __init__(self, seed):
        self.rng = np.random.Generator(np.random.PCG64(seed))
        pool = self.rng.random(size=_POOL, dtype=np.float32)
        pool -= 0.5
        pool *= 2.0 * _HALF_WIDTH
        self.pool = pool.astype(np.float16)

    def __call__(self, shape, mean=0.0):
        size = int(np.prod(shape))
        if size <= _POOL:
            start = int(self.rng.integers(0, _POOL - size + 1))
            values = self.pool[start:start + size]
        else:
            values = np.resize(np.roll(self.pool, int(self.rng.integers(
                0, _POOL))), size)
        values = values.reshape(shape)
        if mean:
            values = (values.astype(np.float32) + mean).astype(np.float16)
        return values


def vit_tensors(config, draw):
    """Google-npz keys for a ViT with a classification head."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    heads = config["num_attention_heads"]
    patch, channels = config["patch_size"], config["num_channels"]
    positions = (config["image_size"] // patch) ** 2 + 1
    out = {
        "cls": draw((1, 1, d)),
        "Transformer/posembed_input/pos_embedding":
            draw((1, positions, d)),
        "embedding/kernel": draw((patch, patch, channels, d)),
        "embedding/bias": draw((d,)),
        "Transformer/encoder_norm/scale": draw((d,), 1.0),
        "Transformer/encoder_norm/bias": draw((d,)),
        "head/kernel": draw((d, config["num_labels"])),
        "head/bias": draw((config["num_labels"],)),
    }
    for i in range(config["num_hidden_layers"]):
        root = f"Transformer/encoderblock_{i}/"
        mha = root + "MultiHeadDotProductAttention_1/"
        out[root + "LayerNorm_0/scale"] = draw((d,), 1.0)
        out[root + "LayerNorm_0/bias"] = draw((d,))
        for name in ("query", "key", "value"):
            out[mha + name + "/kernel"] = draw((d, heads, d // heads))
            out[mha + name + "/bias"] = draw((heads, d // heads))
        out[mha + "out/kernel"] = draw((heads, d // heads, d))
        out[mha + "out/bias"] = draw((d,))
        out[root + "LayerNorm_2/scale"] = draw((d,), 1.0)
        out[root + "LayerNorm_2/bias"] = draw((d,))
        out[root + "MlpBlock_3/Dense_0/kernel"] = draw((d, inner))
        out[root + "MlpBlock_3/Dense_0/bias"] = draw((inner,))
        out[root + "MlpBlock_3/Dense_1/kernel"] = draw((inner, d))
        out[root + "MlpBlock_3/Dense_1/bias"] = draw((d,))
    return out


def gpt2_tensors(config, draw):
    """HF GPT2LMHeadModel state-dict keys; the head is tied to `wte`."""
    d = config["n_embd"]
    inner = config["n_inner"] or 4 * d
    out = {
        "transformer.wte.weight": draw((config["vocab_size"], d)),
        "transformer.wpe.weight": draw((config["n_positions"], d)),
        "transformer.ln_f.weight": draw((d,), 1.0),
        "transformer.ln_f.bias": draw((d,)),
    }
    for i in range(config["n_layer"]):
        root = f"transformer.h.{i}."
        out[root + "ln_1.weight"] = draw((d,), 1.0)
        out[root + "ln_1.bias"] = draw((d,))
        out[root + "attn.c_attn.weight"] = draw((d, 3 * d))
        out[root + "attn.c_attn.bias"] = draw((3 * d,))
        out[root + "attn.c_proj.weight"] = draw((d, d))
        out[root + "attn.c_proj.bias"] = draw((d,))
        out[root + "ln_2.weight"] = draw((d,), 1.0)
        out[root + "ln_2.bias"] = draw((d,))
        out[root + "mlp.c_fc.weight"] = draw((d, inner))
        out[root + "mlp.c_fc.bias"] = draw((inner,))
        out[root + "mlp.c_proj.weight"] = draw((inner, d))
        out[root + "mlp.c_proj.bias"] = draw((d,))
    return out


MAKERS = {"vit": vit_tensors, "gpt2": gpt2_tensors}


def write(config, seed, path):
    """Write the seeded weights of `config` to `path` (an .npz), over
    whatever an earlier run left there."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tensors = MAKERS[config["model_type"]](config, _Source(seed))
    partial = path + ".partial.npz"
    np.savez(partial, **tensors)
    os.replace(partial, path)
    return path
