"""GPT-2 (Radford et al., 2019) forward pass, plainly.

Token and learned position embeddings, pre-norm decoder blocks (causal
multi-head self-attention and a tanh-GELU MLP, each with a residual), a
final layer norm, and the output projection tied to the token embedding.
Reads the HF GPT2LMHeadModel state-dict keys (Conv1D weights are [in, out]).
No cache: every position attends over the whole sequence under a causal
mask. Departure from the paper: none; dropout is off, as at inference."""
import jax
import jax.numpy as jnp
import numpy as np


def _f32(weights, key):
    return jnp.asarray(np.asarray(weights["transformer." + key]),
                       jnp.float32)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def forward(config, weights, ids):
    """Logits [B, S, vocabulary] for token `ids` [B, S], in float32."""
    eps = config["layer_norm_epsilon"]
    heads = config["n_head"]
    ids = jnp.asarray(ids, jnp.int32)
    batch, length = ids.shape
    with jax.default_matmul_precision("highest"):
        wte = _f32(weights, "wte.weight")
        x = wte[ids] + _f32(weights, "wpe.weight")[:length]
        d = x.shape[-1]
        causal = jnp.tril(jnp.ones((length, length), bool))
        for i in range(config["n_layer"]):
            root = f"h.{i}."
            h = _layer_norm(x, _f32(weights, root + "ln_1.weight"),
                            _f32(weights, root + "ln_1.bias"), eps)
            qkv = h @ _f32(weights, root + "attn.c_attn.weight") \
                + _f32(weights, root + "attn.c_attn.bias")
            q, k, v = (part.reshape(batch, length, heads, d // heads)
                       for part in jnp.split(qkv, 3, axis=-1))
            scores = jnp.einsum("bnhe,bmhe->bhnm", q, k) / np.sqrt(d // heads)
            scores = jnp.where(causal, scores, -jnp.inf)
            mixed = jnp.einsum("bhnm,bmhe->bnhe",
                               jax.nn.softmax(scores, axis=-1), v)
            x = x + (mixed.reshape(batch, length, d)
                     @ _f32(weights, root + "attn.c_proj.weight")
                     + _f32(weights, root + "attn.c_proj.bias"))
            h = _layer_norm(x, _f32(weights, root + "ln_2.weight"),
                            _f32(weights, root + "ln_2.bias"), eps)
            h = jax.nn.gelu(h @ _f32(weights, root + "mlp.c_fc.weight")
                            + _f32(weights, root + "mlp.c_fc.bias"),
                            approximate=True)
            x = x + (h @ _f32(weights, root + "mlp.c_proj.weight")
                     + _f32(weights, root + "mlp.c_proj.bias"))
        x = _layer_norm(x, _f32(weights, "ln_f.weight"),
                        _f32(weights, "ln_f.bias"), eps)
        return x @ wte.T
