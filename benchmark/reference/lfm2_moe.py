"""LFM2-MoE (LFM2-8B-A1B's block), forward pass, plainly.

Per layer, all linears without bias, `rms(x; w) = x * rsqrt(mean x^2 +
norm_eps) * w`:

1. `u = rms(x; operator_norm)`, then the layer's mixer (`layer_types`).
   - `"conv"`, the gated short convolution: `[B | C | z] = in_proj u`, three
     chunks of the hidden size in that order; `m = B * z`; `c_t = sum_j
     w[:, 0, j] * m_{t - K + 1 + j}`, `j = 0 .. K - 1` with `K =
     conv_L_cache` (depthwise, causal: `K - 1` zeros before the first
     position; no bias, no activation); `y = out_proj (C * c)`.
   - `"full_attention"`: `q, k, v` projections, `num_attention_heads` query
     and `num_key_value_heads` key/value heads of `hidden / heads`; q and k
     are `rms` over each head's width (`q_layernorm`, `k_layernorm`), then
     turned at their position, the whole head, halves layout, `inv_freq =
     rope_theta**(-2i / head)`; causal softmax in float32 at `head**-0.5`,
     a key/value head shared by `heads / kv_heads` query heads; `out_proj`.
   Residual.
2. `u = rms(x'; ffn_norm)`. A layer below `num_dense_layers`: `w2(silu(w1
   u) * w3 u)`. The others: `s = sigmoid(gate u)` over all experts; the
   `num_experts_per_tok` largest of `s + expert_bias` (ties to the lower
   expert; the bias steers the choice and is no part of the weight);
   weights `s` of the chosen over (their sum + 1e-6), times
   `routed_scaling_factor`; `y = sum_e w_e SwiGLU_e(u)`: no shared expert,
   no drops. Residual.
3. `rms(x; embedding_norm)`, logits over `embed_tokens` (tied).

No cache, no kernel, no code of the program. Sized for a chip that still
holds the program's pipeline: one tensor of a layer at a time, one expert at
a time, each waited for; queries in blocks of 256; logits block by block
into a host array."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256         # queries (and rows of logits) on the device at a time


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate(x, angles):
    """x [S, heads, width] by angles [S, width / 2], halves layout."""
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _short_conv(x, norm, in_proj, kernel, out_proj, eps):
    """Step 1, "conv", for a whole row x [S, D]; kernel [D, 1, K]."""
    length, width = x.shape[0], kernel.shape[-1]
    u = _rms(x, norm, eps)
    before, after, z = jnp.split(u @ in_proj.T, 3, axis=-1)
    m = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype),
                         before * z])
    c = sum(kernel[:, 0, j] * m[j:j + length] for j in range(width))
    return x + (after * c) @ out_proj.T


def _project(x, w, angles, eps, heads, groups):
    """q [S, heads, Dh], k, v [S, groups, Dh] of a whole row x [S, D]."""
    length = x.shape[0]
    u = _rms(x, w["norm"], eps)
    q = (u @ w["q"].T).reshape(length, heads, -1)
    k = (u @ w["k"].T).reshape(length, groups, -1)
    v = (u @ w["v"].T).reshape(length, groups, -1)
    return (_rotate(_rms(q, w["q_norm"], eps), angles),
            _rotate(_rms(k, w["k_norm"], eps), angles), v)


def _attention_block(q, k, v, start):
    """Context [BLOCK, heads * Dh] of the queries at [start, start + BLOCK)
    over all keys, causal."""
    n_q, heads, head = q.shape
    groups = k.shape[1]
    q = q.reshape(n_q, groups, heads // groups, head)
    live = jnp.arange(k.shape[0])[None, :] <= (start
                                               + jnp.arange(n_q))[:, None]
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * head ** -0.5
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    mixed = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)
    return mixed.reshape(n_q, -1)


def _swiglu(u, w1, w2, w3):
    w1, w2, w3 = (w.astype(jnp.float32) for w in (w1, w2, w3))
    return (jax.nn.silu(u @ w1.T) * (u @ w3.T)) @ w2.T


def _add_expert(delta, u, rows, weight, w1, w2, w3):
    """delta[rows] += weight * expert(u[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return delta.at[rows].add(_swiglu(u[rows], w1, w2, w3)
                              * weight[:, None])


def route(u, router, bias, per_tok, scaling):
    """(experts [S, k], weights [S, k]) over all the router's experts."""
    s = jax.nn.sigmoid(u @ router.T)
    _, experts = jax.lax.top_k(s + bias, per_tok)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return experts, chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * scaling


def _head_block(x, norm, table, eps):
    return _rms(x, norm, eps) @ table.T


_ATTENTION = {
    "norm": "operator_norm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight",
    "q_norm": "self_attn.q_layernorm.weight",
    "k_norm": "self_attn.k_layernorm.weight",
}


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict an expert layer and row: the
    `experts` [S, k] chosen and their `weights`."""
    eps = config["norm_eps"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    head = config["hidden_size"] // heads
    per_tok = config["num_experts_per_tok"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    # each step one program, compiled once
    short_conv = jax.jit(_short_conv, static_argnames=("eps",))
    project = jax.jit(_project, static_argnames=("eps", "heads", "groups"))
    attend = jax.jit(_attention_block)
    swiglu = jax.jit(_swiglu)
    router = jax.jit(route, static_argnames=("per_tok", "scaling"))
    add_expert = jax.jit(_add_expert, donate_argnums=0)
    rms = jax.jit(_rms, static_argnames=("eps",))
    head_block = jax.jit(_head_block, static_argnames=("eps",))
    out = np.empty((batch, length, config["vocab_size"]), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    def matrices(root):
        return (np.asarray(weights[f"{root}w{n}.weight"]) for n in (1, 2, 3))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        inv_freq = 1.0 / (float(config["rope_theta"]) ** (
            np.arange(0, head, 2, dtype=np.float32) / head))
        angles = jnp.asarray(np.arange(padded, dtype=np.float32)[:, None]
                             * inv_freq[None])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                if config["layer_types"][i] == "conv":
                    x = short_conv(
                        x, _f32(weights, root + "operator_norm.weight"),
                        _f32(weights, root + "conv.in_proj.weight"),
                        _f32(weights, root + "conv.conv.weight"),
                        _f32(weights, root + "conv.out_proj.weight"), eps=eps)
                    lap("conv", x)
                else:
                    q, k, v = project(
                        x, {name: _f32(weights, root + key)
                            for name, key in _ATTENTION.items()},
                        angles, eps=eps, heads=heads, groups=groups)
                    mixed = jnp.concatenate(
                        [attend(q[start:start + block], k, v, start)
                         for start in range(0, padded, block)])
                    x = x + mixed @ _f32(
                        weights, root + "self_attn.out_proj.weight").T
                    del q, k, v, mixed
                    lap("attention", x)
                u = rms(x, _f32(weights, root + "ffn_norm.weight"), eps=eps)
                if i < config["num_dense_layers"]:
                    x = jax.block_until_ready(
                        x + swiglu(u, *matrices(root + "feed_forward.")))
                    lap("dense")
                    continue
                experts, gates = router(
                    u, _f32(weights, root + "feed_forward.gate.weight"),
                    _f32(weights, root + "feed_forward.expert_bias"),
                    per_tok=per_tok,
                    scaling=float(config["routed_scaling_factor"]))
                chosen, gates = np.asarray(experts), np.asarray(gates)
                if record is not None:
                    record.append({"layer": i, "row": row,
                                   "experts": chosen[:length],
                                   "weights": gates[:length]})
                # one spare row for the padding of an expert's tokens
                delta = jnp.zeros((padded + 1, x.shape[1]), jnp.float32)
                u_spare = jnp.concatenate([u, jnp.zeros_like(u[:1])])
                for e in range(config["num_experts"]):
                    tokens, slot = np.nonzero(chosen == e)
                    if not len(tokens):
                        continue
                    pad = -len(tokens) % 64 if padded > 64 else 0
                    rows = np.concatenate(
                        [tokens, np.full(pad, padded)]).astype(np.int32)
                    weight = np.concatenate(
                        [gates[tokens, slot], np.zeros(pad, np.float32)])
                    delta = jax.block_until_ready(add_expert(
                        delta, u_spare, rows, weight,
                        *matrices(f"{root}feed_forward.experts.{e}.")))
                x = jax.block_until_ready(x + delta[:padded])
                del delta, u, u_spare
                lap("experts")
            norm = _f32(weights, "model.embedding_norm.weight")
            tied = jnp.asarray(table.astype(np.float32))
            for start in range(0, length, block):
                stop = min(start + block, length)
                out[row, start:stop] = np.asarray(head_block(
                    x[start:start + block], norm, tied, eps=eps))[
                        :stop - start]
            del tied, x
            lap("head")
    print("reference lfm2_moe, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
