"""Laguna (Laguna-XS.2's block), forward pass, plainly.

Per layer `i`, all linears without bias, `rms(x; w) = x * rsqrt(mean x^2 +
rms_norm_eps) * w`:

1. `u = rms(x; input_layernorm)`. `q = q_proj u` in
   `num_attention_heads_per_layer[i]` heads of `head_dim`; `k`, `v` in
   `num_key_value_heads`; q and k are `rms` over each head's width (`q_norm`,
   `k_norm`), then turned at their position by the scheme of the layer's
   kind (`layer_types[i]`, `rope_parameters[kind]`), halves layout:
   - `"full_attention"`: the first `partial_rotary_factor` of the head's
     lanes turn (`r` of them), the rest stay. YaRN: `f_j = theta**(-2j/r)`;
     `d(n) = r ln(original / (2 pi n)) / (2 ln theta)`; `low = floor
     d(beta_fast)`, `high = ceil d(beta_slow)`, both held to [0, r - 1];
     `ramp_j = clip((j - low) / (high - low), 0, 1)`; the frequency is `f_j
     / factor * ramp_j + f_j (1 - ramp_j)`; cosine and sine are multiplied
     by `attention_factor`. Every position at or below the query's.
   - `"sliding_attention"`: the whole head turns, `f_j = theta**(-2j /
     head_dim)` with the kind's own theta. Query `t` attends `t -
     sliding_window < p <= t`: a mask over the whole sequence.
   Causal softmax in float32 at `head_dim**-0.5`, a key/value head shared
   by `heads / kv_heads` query heads; each head's output times `sigmoid(g_proj
   u)`, one gate a head; `o_proj`. Residual.
2. `u = rms(x'; post_attention_layernorm)`. A layer whose `mlp_layer_types`
   says "dense": `down(silu(gate u) * up u)`. The others: `s = softmax(gate
   u)` over all experts; the `num_experts_per_tok` largest (ties to the
   lower expert); weights `s[chosen] / sum s[chosen]`, times
   `moe_routed_scaling_factor`; `y = sum_e w_e SwiGLU_e(u)`, no drops; plus
   `sigmoid(shared_expert_gate u) * SwiGLU_shared(u)`. Residual.
3. `rms(x; model.norm)`, logits over `lm_head`.

No cache, no ring, no kernel, no code of the program. Sized for a chip that
still holds the program's pipeline: one tensor of a layer at a time, one
expert at a time, each waited for; attention a query head at a time over
queries in blocks; logits block by block into a host array."""
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
    """(the frequencies of the lanes that turn, what cosine and sine are
    multiplied by) of one kind of layer's `rope_parameters` entry."""
    turned = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, turned, 2, dtype=np.float32)
                            / turned)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {rope['rope_type']!r}")
    original = rope["original_max_position_embeddings"]

    def correction(turns):
        return turned * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), turned - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(turned // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    freqs = plain / rope["factor"] * ramp + plain * (1 - ramp)
    return freqs.astype(np.float32), float(rope["attention_factor"])


def _rotate(x, angles, scale):
    """The first `2 * angles.shape[-1]` lanes of x [S, heads, width] by
    angles [S, r / 2], halves layout; the rest as they are."""
    turned = 2 * angles.shape[-1]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None] * scale
    head, rest = x[..., :turned], x[..., turned:]
    x1, x2 = jnp.split(head, 2, axis=-1)
    return jnp.concatenate(
        [head * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def _project(x, w, angles, eps, heads, groups, scale):
    """q [S, heads, Dh], k, v [S, groups, Dh], the gates [S, heads] of a
    whole row x [S, D]."""
    length = x.shape[0]
    u = _rms(x, w["norm"], eps)
    q = (u @ w["q"].T).reshape(length, heads, -1)
    k = (u @ w["k"].T).reshape(length, groups, -1)
    v = (u @ w["v"].T).reshape(length, groups, -1)
    return (_rotate(_rms(q, w["q_norm"], eps), angles, scale),
            _rotate(_rms(k, w["k_norm"], eps), angles, scale), v,
            jax.nn.sigmoid(u @ w["g"].T))


def _attention_head(q, k, v, start, window):
    """Context [n_q, Dh] of ONE head's queries q [n_q, Dh] at [start, start
    + n_q) over its KV head's k, v [S, Dh]: causal, and inside `window`
    where that is not 0."""
    at = start + jnp.arange(q.shape[0])[:, None]
    key = jnp.arange(k.shape[0])[None, :]
    live = key <= at
    if window:
        live &= key > at - window
    scores = jnp.where(live, (q @ k.T) * q.shape[-1] ** -0.5, -jnp.inf)
    return jax.nn.softmax(scores, -1) @ v


def _swiglu(u, gate, up, down):
    gate, up, down = (w.astype(jnp.float32) for w in (gate, up, down))
    return (jax.nn.silu(u @ gate.T) * (u @ up.T)) @ down.T


def _add_expert(delta, u, rows, weight, gate, up, down):
    """delta[rows] += weight * expert(u[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return delta.at[rows].add(_swiglu(u[rows], gate, up, down)
                              * weight[:, None])


def route(u, router, per_tok, scaling):
    """(experts [S, k], weights [S, k]) over all the router's experts."""
    s = jax.nn.softmax(u @ router.T, -1)
    chosen, experts = jax.lax.top_k(s, per_tok)
    return experts, chosen / chosen.sum(-1, keepdims=True) * scaling


def _shared(u, gate_row, gate, up, down):
    return jax.nn.sigmoid(u @ gate_row.T) * _swiglu(u, gate, up, down)


def _head_block(x, norm, table, eps):
    return _rms(x, norm, eps) @ table.T


_ATTENTION = {
    "norm": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "g": "self_attn.g_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
}


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict an expert layer and row: the
    `experts` [S, k] chosen and their `weights`."""
    eps = config["rms_norm_eps"]
    groups, head = config["num_key_value_heads"], config["head_dim"]
    per_tok = config["num_experts_per_tok"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    # each step one program, compiled once a shape
    project = jax.jit(_project, static_argnames=("eps", "heads", "groups",
                                                 "scale"))
    attend = jax.jit(_attention_head, static_argnames=("window",))
    swiglu = jax.jit(_swiglu)
    router = jax.jit(route, static_argnames=("per_tok", "scaling"))
    add_expert = jax.jit(_add_expert, donate_argnums=0)
    shared = jax.jit(_shared)
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
        return (np.asarray(weights[f"{root}{name}_proj.weight"])
                for name in ("gate", "up", "down"))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        rotation = {}
        for kind, rope in config["rope_parameters"].items():
            if isinstance(rope, dict):
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
                heads = config["num_attention_heads_per_layer"][i]
                window = config["sliding_window"] \
                    if kind == "sliding_attention" else 0
                angles, scale = rotation[kind]
                q, k, v, gates = project(
                    x, {name: _f32(weights, root + key)
                        for name, key in _ATTENTION.items()},
                    angles, eps=eps, heads=heads, groups=groups, scale=scale)
                per = heads // groups
                mixed = jnp.concatenate([jnp.concatenate(
                    [attend(q[start:start + block, h], k[:, h // per],
                            v[:, h // per], start, window=window)
                     for start in range(0, padded, block)])
                    * gates[:, h:h + 1] for h in range(heads)], axis=-1)
                x = x + mixed @ _f32(weights,
                                     root + "self_attn.o_proj.weight").T
                del q, k, v, gates, mixed
                lap("attention", x)
                u = rms(x, _f32(weights,
                                root + "post_attention_layernorm.weight"),
                        eps=eps)
                if config["mlp_layer_types"][i] == "dense":
                    x = jax.block_until_ready(
                        x + swiglu(u, *matrices(root + "mlp.")))
                    lap("dense")
                    continue
                experts, gates = router(
                    u, _f32(weights, root + "mlp.gate.weight"),
                    per_tok=per_tok,
                    scaling=float(config["moe_routed_scaling_factor"]))
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
                        *matrices(f"{root}mlp.experts.{e}.")))
                x = jax.block_until_ready(
                    x + delta[:padded] + shared(
                        u, _f32(weights,
                                root + "mlp.shared_expert_gate.weight"),
                        *matrices(root + "mlp.shared_expert.")))
                del delta, u, u_spare
                lap("experts")
            norm = _f32(weights, "model.norm.weight")
            lm_head = _f32(weights, "lm_head.weight")
            for start in range(0, length, block):
                stop = min(start + block, length)
                out[row, start:stop] = np.asarray(head_block(
                    x[start:start + block], norm, lm_head, eps=eps))[
                        :stop - start]
            del lm_head, x
            lap("head")
    print("reference laguna, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
