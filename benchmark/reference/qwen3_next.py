"""Qwen3-Next, forward pass, plainly: from the equations of the published
`modeling_qwen3_next.py`, the recurrence a position, no chunked form, no
cache, no code of the program.

All linears without bias; `rms0(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`
is the family's zero-centred RMSNorm. Layer `i` is full attention where
`(i + 1) % full_attention_interval == 0`, else Gated DeltaNet:
`h = x + Mixer(rms0(x))`, `x' = h + MoE(rms0(h))`.

1. **Gated DeltaNet.** `in_proj_qkvz` and `in_proj_ba` keep their rows
   grouped by key head: a group is `[q Dk | k Dk | v r Dv | z r Dv]` and
   `[b r | a r]`, `r` value heads a key head; value heads `r j .. r j + r -
   1` belong to key head `j`. `m = [q | k | v]`, flattened, goes through a
   depthwise causal convolution (`conv1d.weight` [channels, 1, K], K - 1
   zeros on the left) and SiLU, and is split again. `q`, `k`: l2-normalised
   a head (`x * rsqrt(sum x^2 + 1e-6)`), repeated to the value heads
   (`repeat_interleave`), `q` scaled by `Dk**-0.5`. A value head: `beta =
   sigmoid(b)`, `g = -exp(A_log) * softplus(a + dt_bias)`; its state `S`
   [Dk, Dv] starts at 0 and, a position: `S <- exp(g) S`; `d = beta (v - S^T
   k)`; `S <- S + k d^T`; `o = S^T q`. Then the gated norm a head, `o *
   rsqrt(mean(o^2) + eps) * w_n * silu(z)` (`w_n` stored about 1), and
   `out_proj`.
2. **Gated full attention.** A head of `q_proj` is `[query | gate]`;
   `q_norm`, `k_norm` are `rms0` over a head; the first
   `partial_rotary_factor` of a head's width is rotated (`rotate_half`,
   `inv_freq = theta**(-2i / R)`); causal softmax in float32, scale
   `Dh**-0.5`, GQA; the heads' outputs times `sigmoid(gate)`, `o_proj`.
3. **Expert layer.** `p = softmax(W_g u)` over all experts; the
   `num_experts_per_tok` largest (ties to the lower expert) over their sum;
   `y = sum_e p_e SwiGLU_e(u) + sigmoid(w_sg . u) SwiGLU_shared(u)`.
4. Final `rms0`, `lm_head` (untied). The checkpoint's `mtp.*` tensors are no
   part of this.

**The chip's share.** The file holds `num_experts` experts a layer from
`experts_held_from`, of the `published.num_experts` the router scores. What
the absent experts would add is left out, and that partial result goes on to
the next layer, as in the program.

Sized for a chip that still holds the program's pipeline: one row at a time,
a layer's tensors one at a time and waited for, the recurrence as a scan
over positions, attention in query blocks of 256, logits block by block into
a host array."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256         # queries (and rows of logits) on the device at a time


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _linear_inputs(x, ln, qkvz_w, ba_w, a_log, dt_bias, eps, hk, dk, dv):
    """Step 1 up to the convolution for a whole row x [S, D]: m [S,
    channels], z [S, Hv, Dv], beta, g [S, Hv]."""
    length = x.shape[0]
    u = _rms0(x, ln, eps)
    qkvz = (u @ qkvz_w.T).reshape(length, hk, -1)
    ba = (u @ ba_w.T).reshape(length, hk, -1)
    per = ba.shape[-1] // 2
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + per * dv]
    z = qkvz[..., 2 * dk + per * dv:].reshape(length, hk * per, dv)
    b, a = ba[..., :per].reshape(length, -1), ba[..., per:].reshape(length, -1)
    m = jnp.concatenate([part.reshape(length, -1) for part in (q, k, v)], -1)
    return m, z, jax.nn.sigmoid(b), \
        -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)


def _convolve(m, conv_w, hk, dk, dv):
    """The depthwise causal convolution and SiLU of m [S, channels], then
    q, k [S, Hv, Dk] (normalised, repeated, q scaled) and v [S, Hv, Dv]."""
    length, width = m.shape[0], conv_w.shape[-1]
    padded = jnp.concatenate([jnp.zeros((width - 1, m.shape[1])), m])
    mixed = jax.nn.silu(sum(conv_w[:, 0, j] * padded[j:j + length]
                            for j in range(width)))
    q = mixed[:, :hk * dk].reshape(length, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(length, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(length, -1, dv)
    per = v.shape[1] // hk

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    return (jnp.repeat(l2(q) * dk ** -0.5, per, axis=1),
            jnp.repeat(l2(k), per, axis=1), v)


def _recurrence(q, k, v, beta, decay):
    """o [S, Hv, Dv] of the delta rule, a position at a time from a zero
    state, `decay` = exp(g) [S, Hv]; sums on the vector unit, exact in
    float32."""
    def step(state, xs):
        q_t, k_t, v_t, beta_t, decay_t = xs
        state = state * decay_t[:, None, None]
        d = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], 1))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], 1)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, beta, decay))[1]


def _linear_output(x, o, z, norm_w, out_w, post_norm, eps):
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * norm_w * jax.nn.silu(z)
    x = x + o.reshape(o.shape[0], -1) @ out_w.T
    return x, _rms0(x, post_norm, eps)


def rotate(x, angles):
    """x [S, heads, Dh] with its first 2 x angles.shape[1] lanes turned,
    halves layout."""
    turned = 2 * angles.shape[1]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None]
    head, rest = x[..., :turned], x[..., turned:]
    x1, x2 = jnp.split(head, 2, axis=-1)
    return jnp.concatenate(
        [head * cos + jnp.concatenate([-x2, x1], -1) * sin, rest], -1)


def _attention_inputs(x, w, angles, eps, heads, groups):
    """Step 2 up to the scores: q [S, H, Dh], gate [S, H * Dh], k, v [S, G,
    Dh]."""
    length = x.shape[0]
    u = _rms0(x, w["ln"], eps)
    q = (u @ w["q"].T).reshape(length, heads, 2, -1)
    q, gate = q[:, :, 0], q[:, :, 1].reshape(length, -1)
    k = (u @ w["k"].T).reshape(length, groups, -1)
    v = (u @ w["v"].T).reshape(length, groups, -1)
    return (rotate(_rms0(q, w["q_norm"], eps), angles), gate,
            rotate(_rms0(k, w["k_norm"], eps), angles), v)


def _attention_block(q, k, v, start):
    """Context [BLOCK, H * Dh] of the queries at [start, start + BLOCK)
    over all keys, causal; query head h reads KV head h // (H / G)."""
    n_q, heads, dh = q.shape
    groups = k.shape[1]
    q = q.reshape(n_q, groups, heads // groups, dh)
    live = jnp.arange(k.shape[0])[None, :] <= (start + jnp.arange(n_q))[:, None]
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * dh ** -0.5
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    mixed = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)
    return mixed.reshape(n_q, -1)


def _attention_output(x, mixed, gate, o_proj, post_norm, eps):
    x = x + (mixed * jax.nn.sigmoid(gate)) @ o_proj.T
    return x, _rms0(x, post_norm, eps)


def route(u, router, per_tok):
    """(experts [S, k], weights [S, k]) over all the router's experts."""
    p, experts = jax.lax.top_k(jax.nn.softmax(u @ router.T, -1), per_tok)
    return experts, p / p.sum(-1, keepdims=True)


def _swiglu(u, gate_w, up_w, down_w):
    gate_w, up_w, down_w = (w.astype(jnp.float32)
                            for w in (gate_w, up_w, down_w))
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def _shared(u, shared_gate, gate_w, up_w, down_w):
    return jax.nn.sigmoid(u @ shared_gate.astype(jnp.float32).T) \
        * _swiglu(u, gate_w, up_w, down_w)


def _add_expert(delta, u, rows, weight, gate_w, up_w, down_w):
    """delta[rows] += weight * expert(u[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return delta.at[rows].add(_swiglu(u[rows], gate_w, up_w, down_w)
                              * weight[:, None])


def _head_block(x, norm, head, eps):
    return _rms0(x, norm, eps) @ head.T


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict a layer and row: the `experts`
    [S, k] chosen and their `weights`."""
    eps = config["rms_norm_eps"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    hk, dk = config["linear_num_key_heads"], config["linear_key_head_dim"]
    dv = config["linear_value_head_dim"]
    per_tok = config["num_experts_per_tok"]
    first = config.get("experts_held_from", 0)
    held = range(first, first + config["num_experts"])
    turned = int(config["head_dim"] * config["partial_rotary_factor"])
    inv_freq = 1.0 / (float(config["rope_theta"]) ** (
        np.arange(0, turned, 2, dtype=np.float32) / turned))
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    # each step one program, compiled once
    linear_inputs = jax.jit(_linear_inputs,
                            static_argnames=("eps", "hk", "dk", "dv"))
    convolve = jax.jit(_convolve, static_argnames=("hk", "dk", "dv"))
    recurrence = jax.jit(_recurrence)
    linear_output = jax.jit(_linear_output, static_argnames=("eps",))
    attention_inputs = jax.jit(_attention_inputs,
                               static_argnames=("eps", "heads", "groups"))
    attend = jax.jit(_attention_block)
    attention_output = jax.jit(_attention_output, static_argnames=("eps",))
    router = jax.jit(route, static_argnames=("per_tok",))
    shared = jax.jit(_shared)
    add_expert = jax.jit(_add_expert, donate_argnums=0)
    head_block = jax.jit(_head_block, static_argnames=("eps",))
    out = np.empty((batch, length, config["vocab_size"]), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    def matrices(root):
        return (np.asarray(weights[root + name + "_proj.weight"])
                for name in ("gate", "up", "down"))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        angles = jnp.asarray(np.arange(padded, dtype=np.float32)[:, None]
                             * inv_freq[None])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                post_norm = _f32(
                    weights, root + "post_attention_layernorm.weight")
                if (i + 1) % config["full_attention_interval"]:
                    att = root + "linear_attn."
                    m, z, beta, g = linear_inputs(
                        x, _f32(weights, root + "input_layernorm.weight"),
                        _f32(weights, att + "in_proj_qkvz.weight"),
                        _f32(weights, att + "in_proj_ba.weight"),
                        _f32(weights, att + "A_log"),
                        _f32(weights, att + "dt_bias"),
                        eps=eps, hk=hk, dk=dk, dv=dv)
                    q, k, v = convolve(
                        m, _f32(weights, att + "conv1d.weight"),
                        hk=hk, dk=dk, dv=dv)
                    del m
                    lap("project", q, k, v)
                    # exp(g) on the host in float64, rounded once: the
                    # factor is applied 32 k times in a row, and an `exp`
                    # a few 1e-7 off with a bias (the chip's float32 one)
                    # leaves a slow head's state 1e-3 off by then
                    decay = jnp.asarray(np.exp(np.asarray(
                        g, np.float64)).astype(np.float32))
                    o = recurrence(q, k, v, beta, decay)
                    del q, k, v, beta, g, decay
                    lap("recurrence", o)
                    x, u = linear_output(
                        x, o, z, _f32(weights, att + "norm.weight"),
                        _f32(weights, att + "out_proj.weight"), post_norm,
                        eps=eps)
                    del o, z
                    lap("project", x, u)
                else:
                    att = root + "self_attn."
                    q, gate, k, v = attention_inputs(
                        x, {"ln": _f32(weights,
                                       root + "input_layernorm.weight"),
                            **{name: _f32(weights, f"{att}{name}_proj.weight")
                               for name in ("q", "k", "v")},
                            **{name: _f32(weights, f"{att}{name}.weight")
                               for name in ("q_norm", "k_norm")}},
                        angles, eps=eps, heads=heads, groups=groups)
                    lap("project", q, gate, k, v)
                    mixed = [attend(q[start:start + block], k, v, start)
                             for start in range(0, padded, block)]
                    x, u = attention_output(
                        x, jnp.concatenate(mixed), gate,
                        _f32(weights, att + "o_proj.weight"), post_norm,
                        eps=eps)
                    del q, gate, k, v, mixed
                    lap("attention", x, u)
                experts, gates = router(
                    u, _f32(weights, root + "mlp.gate.weight"),
                    per_tok=per_tok)
                chosen, gates = np.asarray(experts), np.asarray(gates)
                if record is not None:
                    record.append({"layer": i, "row": row,
                                   "experts": chosen[:length],
                                   "weights": gates[:length]})
                # one spare row for the padding of an expert's tokens
                delta = jnp.concatenate(
                    [shared(u, np.asarray(
                        weights[root + "mlp.shared_expert_gate.weight"]),
                        *matrices(root + "mlp.shared_expert.")),
                     jnp.zeros_like(u[:1])])
                u_spare = jnp.concatenate([u, jnp.zeros_like(u[:1])])
                for e in held:
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
                x = jax.block_until_ready(x + delta[:padded])
                del delta, u, u_spare
                lap("experts")
            norm = _f32(weights, "model.norm.weight")
            head = _f32(weights, "lm_head.weight")
            for start in range(0, length, block):
                stop = min(start + block, length)
                out[row, start:stop] = np.asarray(head_block(
                    x[start:start + block], norm, head, eps=eps))[
                        :stop - start]
            del head, x
            lap("head")
    print("reference qwen3_next, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
