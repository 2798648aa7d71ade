"""Mellum (Mellum2's block), forward pass, plainly.

`x = embed(ids)`. Per layer `i`, all linears without bias, `rms(x; w) = x *
rsqrt(mean x^2 + rms_norm_eps) * w`:

1. `u = rms(x; input_layernorm)`. `q = q_proj u` in `num_attention_heads`
   heads of `head_dim`; `k`, `v` in `num_key_value_heads`; q and k are `rms`
   over each head's width (`q_norm`, `k_norm`), then every lane of the head
   is turned at its position, halves layout, by the scheme of the layer's
   kind (`layer_types[i]`, `rope_parameters[kind]`):
   - `"sliding_attention"`: `f_j = theta**(-2j / head_dim)`. Query `t`
     attends `t - sliding_window < p <= t`: a mask over the whole sequence.
   - `"full_attention"`: every position at or below the query's. YaRN: `d(n)
     = head_dim ln(original / (2 pi n)) / (2 ln theta)`; `low = floor
     d(beta_fast)`, `high = ceil d(beta_slow)`, both held to [0, head_dim -
     1]; `ramp_j = clip((j - low) / (high - low), 0, 1)`; the frequency is
     `f_j / factor * ramp_j + f_j (1 - ramp_j)`; cosine and sine are
     multiplied by `attention_factor`.
   Softmax in float32 at `head_dim**-0.5`, a key/value head shared by
   `heads / kv_heads` query heads; `o_proj`. Residual.
2. `u = rms(x'; post_attention_layernorm)`. `s = softmax(gate u)` over all
   experts; the `num_experts_per_tok` largest (ties to the lower expert);
   weights `s[chosen] / sum s[chosen]`; `y = sum_e w_e down_e(silu(gate_e u)
   * up_e u)`, every expert over every token and the unchosen weighted 0:
   no drops, no sort, no gather. Residual.
3. `rms(x; model.norm)`, logits over `lm_head`.

No cache, no ring, no kernel, no code of the program. Sized for a chip that
the served program has left: one layer's tensors on the device at a time
(its 64 experts together, 1.6 GB in float32), attention over queries in
blocks, logits block by block into a host array."""
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512         # queries (and rows of logits) on the device at a time


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def frequencies(rope, head_dim):
    """(the `head_dim / 2` frequencies, what cosine and sine are multiplied
    by) of one kind of layer's `rope_parameters` entry."""
    theta = float(rope["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {rope['rope_type']!r}")
    original = rope["original_max_position_embeddings"]

    def correction(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    freqs = plain / rope["factor"] * ramp + plain * (1 - ramp)
    return freqs.astype(np.float32), float(rope["attention_factor"])


def _rotate(x, angles, scale):
    """x [S, heads, width] by angles [S, width / 2], halves layout."""
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _project(x, w, angles, eps, heads, groups, scale):
    """q [S, heads, Dh], k, v [S, groups, Dh] of a whole row x [S, D]."""
    length = x.shape[0]
    u = _rms(x, w["norm"], eps)
    q = (u @ w["q"].T).reshape(length, heads, -1)
    k = (u @ w["k"].T).reshape(length, groups, -1)
    v = (u @ w["v"].T).reshape(length, groups, -1)
    return (_rotate(_rms(q, w["q_norm"], eps), angles, scale),
            _rotate(_rms(k, w["k_norm"], eps), angles, scale), v)


def _attend(q, k, v, start, window):
    """Context [n_q, heads * Dh] of the queries q [n_q, heads, Dh] at [start,
    start + n_q) over k, v [S, groups, Dh]: causal, and inside `window` where
    that is not 0; query head h reads KV head h // (heads / groups)."""
    n_q, heads, width = q.shape
    groups = k.shape[1]
    at = start + jnp.arange(n_q)[:, None]
    key = jnp.arange(k.shape[0])[None, :]
    live = key <= at
    if window:
        live &= key > at - window
    q = q.reshape(n_q, groups, heads // groups, width)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * width ** -0.5
    probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), -1)
    return jnp.einsum("grqk,kgd->qgrd", probs, v).reshape(n_q, -1)


def route(u, router, per_tok):
    """The weight [S, experts] of every expert for every token: its
    renormalised softmax score where it is among the token's `per_tok`
    largest, else 0; and the chosen [S, per_tok]."""
    s = jax.nn.softmax(u @ router.T, -1)
    chosen, experts = jax.lax.top_k(s, per_tok)
    weight = chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, experts].set(weight), experts


def _experts(u, weight, gate, up, down):
    """sum_e weight[:, e] * down_e(silu(gate_e u) * up_e u), an expert
    after another over all of u [S, D]."""
    def one(total, xs):
        w, g, p, d = xs
        return total + w[:, None] * ((jax.nn.silu(u @ g.T) * (u @ p.T))
                                     @ d.T), None
    total, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            (weight.T, gate, up, down))
    return total


def _head_block(x, norm, table, eps):
    return _rms(x, norm, eps) @ table.T


_ATTENTION = {
    "norm": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
}


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict a layer and row: the `experts`
    [S, k] chosen."""
    eps = config["rms_norm_eps"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    head = config["head_dim"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    project = jax.jit(_project, static_argnames=("eps", "heads", "groups",
                                                 "scale"))
    attend = jax.jit(_attend, static_argnames=("window",))
    router = jax.jit(route, static_argnames=("per_tok",))
    experts = jax.jit(_experts)
    rms = jax.jit(_rms, static_argnames=("eps",))
    head_block = jax.jit(_head_block, static_argnames=("eps",))
    out = np.empty((batch, length, config["vocab_size"]), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    def stacked(root, name):
        """A layer's experts' `name` matrices [experts, out, in], float32."""
        return jnp.asarray(np.stack([
            np.asarray(weights[f"{root}mlp.experts.{e}.{name}_proj.weight"],
                       np.float32) for e in range(config["num_experts"])]))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        rotation = {}
        for kind, rope in config["rope_parameters"].items():
            freqs, scale = frequencies(rope, head)
            rotation[kind] = (jnp.asarray(
                np.arange(padded, dtype=np.float32)[:, None]
                * freqs[None]), scale)
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                kind = config["layer_types"][i]
                window = config["sliding_window"] \
                    if kind == "sliding_attention" else 0
                angles, scale = rotation[kind]
                q, k, v = project(
                    x, {name: _f32(weights, root + key)
                        for name, key in _ATTENTION.items()},
                    angles, eps=eps, heads=heads, groups=groups, scale=scale)
                mixed = jnp.concatenate(
                    [attend(q[start:start + block], k, v, start,
                            window=window)
                     for start in range(0, padded, block)])
                x = x + mixed @ _f32(weights,
                                     root + "self_attn.o_proj.weight").T
                del q, k, v, mixed
                lap("attention", x)
                u = rms(x, _f32(weights,
                                root + "post_attention_layernorm.weight"),
                        eps=eps)
                weight, chosen = router(
                    u, _f32(weights, root + "mlp.gate.weight"),
                    per_tok=config["num_experts_per_tok"])
                if record is not None:
                    record.append({"layer": i, "row": row,
                                   "experts": np.asarray(chosen)[:length]})
                x = x + experts(u, weight, stacked(root, "gate"),
                                stacked(root, "up"), stacked(root, "down"))
                del u, weight
                lap("experts", x)
            norm = _f32(weights, "model.norm.weight")
            lm_head = _f32(weights, "lm_head.weight")
            for start in range(0, length, block):
                stop = min(start + block, length)
                out[row, start:stop] = np.asarray(head_block(
                    x[start:start + block], norm, lm_head, eps=eps))[
                        :stop - start]
            del lm_head, x
            lap("head")
    print("reference mellum, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
