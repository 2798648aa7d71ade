"""Nemotron-H (Nemotron-3-Super), forward pass, plainly: from the equations
the configuration's `assumed` writes down (the published config.json, Mamba-2's
reference code, DeepSeek-V3's router), the recurrence a position, no chunked
form, no cache, no code of the program.

All linears without bias; `rms(x) = x * rsqrt(mean(x^2) + eps) * w`. Layer
`i` is ONE sublayer, what letter `i` of `hybrid_override_pattern` says:
`h <- h + mixer_i(rms(h; norm_i))`; then `logits = lm_head(rms(h; norm_f))`.

1. **`M`, Mamba-2.** `[z | xBC | dt] = in_proj(u)`, `H P`, `H P + 2 G N` and
   `H` wide. `xBC = silu(conv(xBC) + bias)`: depthwise, causal
   (`conv1d.weight` [channels, 1, K], K - 1 zeros on the left). `[x | B | C]
   = xBC`: `x_t[h]` P wide, `B_t[g]`, `C_t[g]` N wide; heads `(H / G) g ..
   (H / G)(g + 1) - 1` read group `g`. `dt_t[h] = softplus(dt_t[h] +
   dt_bias[h])`, no clamp; `a_t[h] = exp(-exp(A_log[h]) dt_t[h])`. A head's
   state `S` [P, N] starts at 0 and, a position: `S <- a_t S + dt_t x_t
   B_t^T`; `y_t = S C_t + D x_t`. Then `y <- y * silu(z)` (the gate BEFORE the
   norm), an RMSNorm over each group's `H P / G` lanes times `mixer.norm
   .weight`, and `out_proj`.
2. **`*`, attention.** `q` H heads, `k`, `v` G heads of `head_dim`, no bias,
   no q/k norm, no rotation (the configuration's `attn_use_rope` is false,
   and a file that sets it is refused: the program has no rotation, so the
   two would only part); causal softmax at `head_dim**-0.5`, GQA; `o_proj`.
3. **`E`, latent experts.** `s = sigmoid(W_r u)` over all the router's
   experts; the `num_experts_per_tok` largest of `s + e_score_correction_bias`
   (ties to the lower expert); `g_e = routed_scaling_factor s_e / (sum of the
   chosen s + 1e-20)`; `c = fc1_latent u`; `r = sum_e g_e W2_e relu(W1_e
   c)^2` over the chosen experts the file holds; `out = fc2_latent r + Ws2
   relu(Ws1 u)^2`.

**The chip's share.** The file holds `n_routed_experts` experts a layer from
`experts_held_from`, of the `published.n_routed_experts` the router scores.
What the absent experts would add is left out, and that partial result goes
on to the next layer, as in the program.

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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _mamba_inputs(x, ln, in_proj, conv_w, conv_b, a_log, dt_bias, eps,
                  inner, groups, state):
    """Step 1 up to the recurrence for a whole row x [S, D]: z [S, H P], x
    [S, H P], B, C [S, G, N], dt, log a [S, H]."""
    length, width = x.shape[0], conv_w.shape[-1]
    u = _rms(x, ln, eps)
    proj = u @ in_proj.T
    z, xbc, dt = proj[:, :inner], proj[:, inner:-a_log.shape[0]], \
        proj[:, -a_log.shape[0]:]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(conv_w[:, 0, j] * padded[j:j + length]
                          for j in range(width)) + conv_b)
    b = xbc[:, inner:inner + groups * state].reshape(length, groups, state)
    c = xbc[:, inner + groups * state:].reshape(length, groups, state)
    dt = jax.nn.softplus(dt + dt_bias)
    return z, xbc[:, :inner], b, c, dt, -jnp.exp(a_log) * dt


def _recurrence(x, b, c, dt, decay, d_skip):
    """y [S, H, P] of the recurrence, a position at a time from a zero state:
    x [S, H, P], b, c [S, G, N], dt, decay = a [S, H]; sums on the vector
    unit, exact in float32."""
    per = x.shape[1] // b.shape[1]

    def step(s, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        b_t, c_t = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        s = a_t[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1) + d_skip[:, None] * x_t

    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (x, b, c, dt, decay))[1]


def _mamba_output(x, y, z, norm_w, out_proj, eps, groups):
    length = x.shape[0]
    y = (y.reshape(length, -1) * jax.nn.silu(z)).reshape(length, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return x + (y.reshape(length, -1) * norm_w) @ out_proj.T


def _attention_inputs(x, w, eps, heads, groups):
    """Step 2 up to the scores: q [S, H, Dh], k, v [S, G, Dh]."""
    length = x.shape[0]
    u = _rms(x, w["ln"], eps)
    return ((u @ w["q"].T).reshape(length, heads, -1),
            (u @ w["k"].T).reshape(length, groups, -1),
            (u @ w["v"].T).reshape(length, groups, -1))


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


def route(u, router, bias, per_tok, scale):
    """(experts [S, k], gates [S, k]) over all the router's experts."""
    s = jax.nn.sigmoid(u @ router.T)
    _, experts = jax.lax.top_k(s + bias, per_tok)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return experts, scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def expert(c, up_w, down_w):
    up_w, down_w = up_w.astype(jnp.float32), down_w.astype(jnp.float32)
    return relu2(c @ up_w.T) @ down_w.T


def _add_expert(r, c, rows, weight, up_w, down_w):
    """r[rows] += weight * expert(c[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return r.at[rows].add(expert(c[rows], up_w, down_w) * weight[:, None])


def _expert_output(x, u, r, fc2, up_w, down_w):
    return x + r @ fc2.T + expert(u, up_w, down_w)


def _head_block(x, norm, head, eps):
    return _rms(x, norm, eps) @ head.T


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict an expert layer and row: the
    `experts` [S, k] chosen and their `weights`."""
    eps = config["layer_norm_epsilon"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    ssm_heads, ssm_groups = config["mamba_num_heads"], config["n_groups"]
    inner, state = ssm_heads * config["mamba_head_dim"], \
        config["ssm_state_size"]
    per_tok = config["num_experts_per_tok"]
    scale = float(config["routed_scaling_factor"])
    first = config.get("experts_held_from", 0)
    held = range(first, first + config["n_routed_experts"])
    pattern = config["hybrid_override_pattern"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    if config.get("attn_use_rope"):
        raise ValueError(
            "attn_use_rope: neither pipeedge_tpu/models/nemotron_h.py nor "
            "this reference rotates q and k; write both before setting it")
    # each step one program, compiled once
    mamba_inputs = jax.jit(_mamba_inputs, static_argnames=(
        "eps", "inner", "groups", "state"))
    recurrence = jax.jit(_recurrence)
    mamba_output = jax.jit(_mamba_output, static_argnames=("eps", "groups"))
    attention_inputs = jax.jit(_attention_inputs,
                               static_argnames=("eps", "heads", "groups"))
    attend = jax.jit(_attention_block)
    router = jax.jit(route, static_argnames=("per_tok", "scale"))
    add_expert = jax.jit(_add_expert, donate_argnums=0)
    expert_inputs = jax.jit(_rms, static_argnames=("eps",))
    expert_output = jax.jit(_expert_output)
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
                for name in ("up", "down"))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["backbone.embeddings.weight"])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"backbone.layers.{i}."
                mix = root + "mixer."
                ln = _f32(weights, root + "norm.weight")
                if pattern[i] == "M":
                    z, xs, b, c, dt, la = mamba_inputs(
                        x, ln, _f32(weights, mix + "in_proj.weight"),
                        _f32(weights, mix + "conv1d.weight"),
                        _f32(weights, mix + "conv1d.bias"),
                        _f32(weights, mix + "A_log"),
                        _f32(weights, mix + "dt_bias"), eps=eps, inner=inner,
                        groups=ssm_groups, state=state)
                    lap("project", z, xs, b, c, dt, la)
                    # the decay on the host in float64, rounded once: it is
                    # applied a position after another, and an `exp` a few
                    # 1e-7 off with a bias (the chip's float32 one) leaves a
                    # slow head's state off by then
                    decay = jnp.asarray(np.exp(np.asarray(
                        la, np.float64)).astype(np.float32))
                    y = recurrence(xs.reshape(padded, ssm_heads, -1), b, c,
                                   dt, decay, _f32(weights, mix + "D"))
                    del xs, b, c, dt, la, decay
                    lap("recurrence", y)
                    x = mamba_output(
                        x, y, z, _f32(weights, mix + "norm.weight"),
                        _f32(weights, mix + "out_proj.weight"), eps=eps,
                        groups=ssm_groups)
                    del y, z
                    lap("project", x)
                elif pattern[i] == "*":
                    q, k, v = attention_inputs(
                        x, {"ln": ln, **{
                            name: _f32(weights, f"{mix}{name}_proj.weight")
                            for name in ("q", "k", "v")}},
                        eps=eps, heads=heads, groups=groups)
                    mixed = [attend(q[start:start + block], k, v, start)
                             for start in range(0, padded, block)]
                    x = jax.block_until_ready(
                        x + jnp.concatenate(mixed)
                        @ _f32(weights, mix + "o_proj.weight").T)
                    del q, k, v, mixed
                    lap("attention", x)
                else:
                    u = expert_inputs(x, ln, eps=eps)
                    experts, gates = router(
                        u, _f32(weights, mix + "gate.weight"),
                        _f32(weights, mix + "gate.e_score_correction_bias"),
                        per_tok=per_tok, scale=scale)
                    chosen, gates = np.asarray(experts), np.asarray(gates)
                    if record is not None:
                        record.append({"layer": i, "row": row,
                                       "experts": chosen[:length],
                                       "weights": gates[:length]})
                    # one spare row for the padding of an expert's tokens
                    c = jnp.concatenate(
                        [u @ _f32(weights, mix + "fc1_latent_proj.weight").T,
                         jnp.zeros((1, config["moe_latent_size"]))])
                    r = jnp.zeros_like(c)
                    for e in held:
                        tokens, slot = np.nonzero(chosen == e)
                        if not len(tokens):
                            continue
                        pad = -len(tokens) % 64 if padded > 64 else 0
                        rows = np.concatenate(
                            [tokens, np.full(pad, padded)]).astype(np.int32)
                        weight = np.concatenate(
                            [gates[tokens, slot], np.zeros(pad, np.float32)])
                        r = jax.block_until_ready(add_expert(
                            r, c, rows, weight,
                            *matrices(f"{mix}experts.{e}.")))
                    x = jax.block_until_ready(expert_output(
                        x, u, r[:padded],
                        _f32(weights, mix + "fc2_latent_proj.weight"),
                        *matrices(mix + "shared_experts.")))
                    del u, c, r
                    lap("experts")
            norm = _f32(weights, "backbone.norm_f.weight")
            head = _f32(weights, "lm_head.weight")
            for start in range(0, length, block):
                stop = min(start + block, length)
                out[row, start:stop] = np.asarray(head_block(
                    x[start:start + block], norm, head, eps=eps))[
                        :stop - start]
            del head, x
            lap("head")
    print("reference nemotron_h, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
