"""Granite 4.0-H (granite-4.0-h-micro), forward pass, plainly: from the
equations the configuration's `assumed` writes down (the published
config.json, Mamba-2's reference code, the Granite line's multipliers), the
recurrence a position, no chunked form, no cache, no kernel, no code of the
program.

All linears without bias; `rms(x; w) = x * rsqrt(mean(x^2) + eps) * w`.
  h0 = embedding_multiplier * embed[ids]
  block i:  h = h + r * Mixer_i(rms(h; input_layernorm))
            h = h + r * W_out (silu(W_gate u) * (W_up u)),
                u = rms(h; post_attention_layernorm),
                [W_gate; W_up] = shared_mlp.input_linear (gate's rows first)
  logits = (rms(h; norm) @ embed^T) / logits_scaling
with `r` = `residual_multiplier` on both branches and the head tied.

1. **"mamba", Mamba-2.** `[z | xBC | dt] = in_proj(u)`, `H P`, `H P + 2 G N`
   and `H` wide. `xBC = silu(conv(xBC) + bias)`: depthwise, causal
   (`conv1d.weight` [channels, 1, K], K - 1 zeros on the left). `[x | B | C]
   = xBC`: `x_t[h]` P wide, `B_t[g]`, `C_t[g]` N wide; heads `(H / G) g ..
   (H / G)(g + 1) - 1` read group `g` (`mamba_n_groups` 1: every head reads
   the one). `dt_t[h] = softplus(dt_t[h] + dt_bias[h])`, no clamp; `a_t[h] =
   exp(-exp(A_log[h]) dt_t[h])`. A head's state `S` [P, N] starts at 0 and,
   a position: `S <- a_t S + dt_t x_t B_t^T`; `y_t = S C_t + D x_t`. Then `y
   <- y * silu(z)` (the gate BEFORE the norm), an RMSNorm over each group's
   `H P / G` lanes times `mamba.norm.weight`, and `out_proj`.
2. **"attention".** `q` H heads, `k`, `v` G heads of `hidden / H`, no bias,
   no q/k norm, NO rotation (`position_embedding_type` "nope"; a file that
   names another is refused: the program has none, so the two would only
   part); causal softmax of `q k^T * attention_multiplier`, GQA; `o_proj`.

Departures from the published description: none in the equations. What the
description leaves open is the configuration's `assumed` (the order gate
then up inside `input_linear`, `[z | xBC | dt]` inside `in_proj`, no
`time_step_limit`). A configuration with routed experts (`num_local_experts`
> 0) is refused: this is the dense member's forward pass.

Sized for a chip that still holds the program's pipeline (12.4 GB of 16):
one row at a time, a block's tensors one at a time and waited for, the
recurrence as a scan over positions, attention in query blocks of 256, the
SwiGLU a half of its width at a time, the tied table's logits a block of
the vocabulary at a time into a host array (the table in float32 is 0.82
GB). Six programs in all, each compiled once: a first run of the cell
compiles them inside its time limit."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256             # queries on the device at a time
VOCAB_BLOCK = 14336     # rows of the table on the device at a time (7 x 2,048:
                        # the published 100,352 rows are seven whole blocks)


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def _mamba_output(x, y, z, norm_w, out_proj, eps, groups, residual):
    length = x.shape[0]
    y = (y.reshape(length, -1) * jax.nn.silu(z)).reshape(length, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return x + residual * ((y.reshape(length, -1) * norm_w) @ out_proj.T)


def _mamba_rest(x, xs, b, c, dt, decay, z, d_skip, norm_w, out_proj, eps,
                groups, residual):
    """Step 1 from the recurrence on, one program (a first run compiles
    every program of this file inside the cell's time limit)."""
    y = _recurrence(xs.reshape(xs.shape[0], decay.shape[1], -1), b, c, dt,
                    decay, d_skip)
    return _mamba_output(x, y, z, norm_w, out_proj, eps, groups, residual)


def _attention_inputs(x, w, eps, heads, groups):
    """Step 2 up to the scores: q [S, H, Dh], k, v [S, G, Dh]; no rotation."""
    length = x.shape[0]
    u = _rms(x, w["ln"], eps)
    return ((u @ w["q"].T).reshape(length, heads, -1),
            (u @ w["k"].T).reshape(length, groups, -1),
            (u @ w["v"].T).reshape(length, groups, -1))


def _attention_block(q, k, v, start, scale):
    """Context [BLOCK, H * Dh] of the queries at [start, start + BLOCK)
    over all keys, causal; query head h reads KV head h // (H / G)."""
    n_q, heads, dh = q.shape
    groups = k.shape[1]
    q = q.reshape(n_q, groups, heads // groups, dh)
    live = jnp.arange(k.shape[0])[None, :] <= (start + jnp.arange(n_q))[:, None]
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * scale
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    mixed = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)
    return mixed.reshape(n_q, -1)


def _attention_layer(x, w, eps, heads, groups, scale, residual, block):
    """Step 2 for a whole row x [S, D], the queries a block at a time."""
    q, k, v = _attention_inputs(x, w, eps, heads, groups)
    mixed = jnp.concatenate([
        _attention_block(q[start:start + block], k, v, start, scale)
        for start in range(0, x.shape[0], block)])
    return x + residual * (mixed @ w["o"].T)


def _swiglu_part(acc, x, ln, gate_w, up_w, down_w, eps, residual):
    """acc + r W_out[:, part] (silu(W_gate[part] u) * (W_up[part] u)) with
    u = rms(x; ln) of the block's input `x`: `acc` starts as `x` and takes
    the feed-forward part's width a part at a time."""
    u = _rms(x, ln, eps)
    return acc + residual * (
        (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T)


def _head_block(x, norm, table, eps, scaling):
    return (_rms(x, norm, eps) @ table.T) / scaling


def forward(config, weights, ids):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array."""
    if config.get("num_local_experts"):
        raise ValueError("num_local_experts: this reference is the dense "
                         "member's; it has no routed experts")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError(
            "position_embedding_type: neither pipeedge_tpu/models/"
            "granite_hybrid.py nor this reference rotates q and k; write "
            "both before naming another")
    eps = config["rms_norm_eps"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    ssm_heads, ssm_groups = config["mamba_n_heads"], config["mamba_n_groups"]
    inner, state = ssm_heads * config["mamba_d_head"], \
        config["mamba_d_state"]
    width = config["shared_intermediate_size"]
    residual = float(config["residual_multiplier"])
    scale = float(config["attention_multiplier"])
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    # each step one program, compiled once: six in all
    mamba_inputs = jax.jit(_mamba_inputs, static_argnames=(
        "eps", "inner", "groups", "state"))
    mamba_rest = jax.jit(_mamba_rest,
                         static_argnames=("eps", "groups", "residual"))
    attention_layer = jax.jit(_attention_layer, static_argnames=(
        "eps", "heads", "groups", "scale", "residual", "block"))
    swiglu_part = jax.jit(_swiglu_part, static_argnames=("eps", "residual"))
    head_block = jax.jit(_head_block, static_argnames=("eps", "scaling"))
    vocab = config["vocab_size"]
    out = np.empty((batch, length, vocab), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]].astype(np.float32) \
                * np.float32(config["embedding_multiplier"])
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                ln = _f32(weights, root + "input_layernorm.weight")
                if config["layer_types"][i] == "mamba":
                    mix = root + "mamba."
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
                    x = mamba_rest(
                        x, xs, b, c, dt, decay, z, _f32(weights, mix + "D"),
                        _f32(weights, mix + "norm.weight"),
                        _f32(weights, mix + "out_proj.weight"), eps=eps,
                        groups=ssm_groups, residual=residual)
                    del xs, b, c, dt, la, decay, z
                    lap("recurrence", x)
                elif config["layer_types"][i] == "attention":
                    mix = root + "self_attn."
                    x = attention_layer(
                        x, {"ln": ln, **{
                            name: _f32(weights, f"{mix}{name}_proj.weight")
                            for name in ("q", "k", "v", "o")}},
                        eps=eps, heads=heads, groups=groups, scale=scale,
                        residual=residual, block=block)
                    lap("attention", x)
                else:
                    raise ValueError(f"layer {i}: no mixer "
                                     f"{config['layer_types'][i]!r}")
                post = _f32(weights, root + "post_attention_layernorm.weight")
                fused = weights[root + "shared_mlp.input_linear.weight"]
                down = weights[root + "shared_mlp.output_linear.weight"]
                half = -(-width // 2)
                acc = x
                for at in range(0, width, half):
                    part = slice(at, min(at + half, width))
                    acc = jax.block_until_ready(swiglu_part(
                        acc, x, post,
                        jnp.asarray(np.asarray(fused[part], np.float32)),
                        jnp.asarray(np.asarray(
                            fused[width + part.start:width + part.stop],
                            np.float32)),
                        jnp.asarray(np.asarray(down[:, part], np.float32)),
                        eps=eps, residual=residual))
                x = acc
                del acc, post, fused, down
                lap("swiglu", x)
            norm = _f32(weights, "model.norm.weight")
            scaling = float(config["logits_scaling"])
            for first in range(0, vocab, VOCAB_BLOCK):
                rows = jnp.asarray(np.asarray(
                    table[first:first + VOCAB_BLOCK], np.float32))
                for start in range(0, length, block):
                    stop = min(start + block, length)
                    out[row, start:stop, first:first + rows.shape[0]] = \
                        np.asarray(head_block(
                            x[start:start + block], norm, rows, eps=eps,
                            scaling=scaling))[:stop - start]
                del rows
            del x
            lap("head")
    print("reference granite_hybrid, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
