"""ViT (Dosovitskiy et al., arXiv:2010.11929) forward pass, plainly.

Patches are embedded by a strided convolution, a class token is prepended,
learned position embeddings are added, then pre-norm encoder blocks
(multi-head self-attention and a GELU MLP, each with a residual), a final
layer norm, and a linear head on the class token. Reads the Google npz key
scheme the paper's checkpoints are published in. Departure from the paper:
none; dropout is off, as at inference."""
import jax
import jax.numpy as jnp
import numpy as np


def _f32(weights, key):
    return jnp.asarray(np.asarray(weights[key]), jnp.float32)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def forward(config, weights, images):
    """Logits [B, labels] for `images` [B, C, H, W], all in float32."""
    eps = config["layer_norm_eps"]
    heads = config["num_attention_heads"]
    patch = config["patch_size"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(images, jnp.float32)
        kernel = _f32(weights, "embedding/kernel")          # [ph, pw, C, D]
        x = jax.lax.conv_general_dilated(
            x, kernel, window_strides=(patch, patch), padding="VALID",
            dimension_numbers=("NCHW", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        x = x + _f32(weights, "embedding/bias")
        batch, d = x.shape[0], x.shape[-1]
        x = x.reshape(batch, -1, d)                         # row-major patches
        cls = jnp.broadcast_to(_f32(weights, "cls"), (batch, 1, d))
        x = jnp.concatenate([cls, x], axis=1)
        x = x + _f32(weights, "Transformer/posembed_input/pos_embedding")
        for i in range(config["num_hidden_layers"]):
            root = f"Transformer/encoderblock_{i}/"
            mha = root + "MultiHeadDotProductAttention_1/"
            h = _layer_norm(x, _f32(weights, root + "LayerNorm_0/scale"),
                            _f32(weights, root + "LayerNorm_0/bias"), eps)
            q, k, v = (jnp.einsum("bnd,dhe->bnhe", h,
                                  _f32(weights, mha + name + "/kernel"))
                       + _f32(weights, mha + name + "/bias")
                       for name in ("query", "key", "value"))
            scores = jnp.einsum("bnhe,bmhe->bhnm", q, k) / np.sqrt(d // heads)
            mixed = jnp.einsum("bhnm,bmhe->bnhe",
                               jax.nn.softmax(scores, axis=-1), v)
            x = x + (jnp.einsum("bnhe,hed->bnd", mixed,
                                _f32(weights, mha + "out/kernel"))
                     + _f32(weights, mha + "out/bias"))
            h = _layer_norm(x, _f32(weights, root + "LayerNorm_2/scale"),
                            _f32(weights, root + "LayerNorm_2/bias"), eps)
            h = jax.nn.gelu(
                h @ _f32(weights, root + "MlpBlock_3/Dense_0/kernel")
                + _f32(weights, root + "MlpBlock_3/Dense_0/bias"),
                approximate=False)
            x = x + (h @ _f32(weights, root + "MlpBlock_3/Dense_1/kernel")
                     + _f32(weights, root + "MlpBlock_3/Dense_1/bias"))
        x = _layer_norm(x, _f32(weights, "Transformer/encoder_norm/scale"),
                        _f32(weights, "Transformer/encoder_norm/bias"), eps)
        return x[:, 0] @ _f32(weights, "head/kernel") \
            + _f32(weights, "head/bias")
