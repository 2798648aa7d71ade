"""Brumby-14B-Base (power retention), forward pass, plainly: the ATTENTION
form of the configuration's `assumed` equations, O(T^2) a row, with no
state, no expanded keys (`phi` is never formed), no chunks, no cache, no
kernel and no code of the program.

All linears without bias; `rms(x; w) = x * rsqrt(mean(x^2) + eps) * w`.
  h0 = embed[ids]
  layer i:  h = h + o_proj(y),  y = Retention(rms(h; input_layernorm))
            h = h + down(silu(gate u) * up u),  u = rms(h; post_attention_layernorm)
  logits = rms(h; norm) @ lm_head^T            (two tables)

**Retention**, `H` query heads and `G` KV heads of `hd` (query head `h` reads
KV head `h // (H / G)`): `q`, `k`, `v` from `q_proj`, `k_proj`, `v_proj`; q
and k RMS-normed a head (`q_norm`, `k_norm`) and rotated (halves layout,
`rotate_half`, the whole head, base `rope_theta`, absolute position); a gate
a KV head `a_t = log sigmoid(g_proj u_t)`. Then, a query head at `t`:
  A_t = sum_(i<=t) a_i
  w_tj = exp(A_t - A_j) (q_t . k_j / sqrt(hd))^2        for j <= t, else 0
  y_t  = sum_j w_tj v_j / (sum_j w_tj + eps)
Degree 2: every weight is >= 0, no softmax. No output gate, no output norm.

Departures from the published description: none in the equations the
configuration writes down; what the published config leaves open is its
`assumed`.

**The decay on the host**: `A` is a running sum over the whole row (down to
about -1,600 at 1,792 positions with seeded weights), so `A_t - A_j` is
taken in float64 and `exp` of it rounded to float32 once; in float32 the
difference alone would be 1e-4 off.

Sized for a chip that still holds the program's pipeline (12.45 GB of 16):
one row at a time, a tensor at a time (a float32 layer is 1.32 GB: `q_proj`
105 MB, a quarter of an FFN matrix 89 MB), each waited for, the queries in
blocks of 256 (a block's weights over 1,792 keys in 40 heads are 73 MB), the
SwiGLU a quarter of its width at a time, the head's table sixteen blocks of
rows at a time into a host array (the float32 head is 3.1 GB). Five
programs in all, each compiled once."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256             # queries on the device at a time
FFN_PARTS = 4           # parts of the SwiGLU's width on the device at a time
VOCAB_BLOCKS = 16       # blocks of the head's rows (151,936 = 16 x 9,496)
EPS = 1e-6              # the guard of the weights' sum (`assumed.sum_of_keys`)


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    """x [S, heads, hd] turned by cos, sin [S, hd]: HF's `rotate_half`."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _project(x, ln, wq, wk, wv, wg, q_norm, k_norm, cos, sin, eps, heads,
             groups):
    """q [S, H, hd], k, v [S, G, hd] and the log gate a [S, G] of a row."""
    length = x.shape[0]
    u = _rms(x, ln, eps)
    q = _rotate(_rms((u @ wq.T).reshape(length, heads, -1), q_norm, eps),
                cos, sin)
    k = _rotate(_rms((u @ wk.T).reshape(length, groups, -1), k_norm, eps),
                cos, sin)
    v = (u @ wv.T).reshape(length, groups, -1)
    return q, k, v, jax.nn.log_sigmoid(u @ wg.T)


def _retention_block(q, k, v, decay):
    """y [BLOCK, H * hd] of the queries q [BLOCK, H, hd] over all keys:
    `decay` [G, BLOCK, S] is exp(A_t - A_j) where j <= t and 0 elsewhere."""
    n_q, heads, hd = q.shape
    groups = k.shape[1]
    q = q.reshape(n_q, groups, heads // groups, hd)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / jnp.sqrt(jnp.float32(hd))
    weights = scores * scores * decay[:, None]
    mixed = jnp.einsum("grqk,kgd->qgrd", weights, v)
    total = jnp.transpose(jnp.sum(weights, -1), (2, 0, 1))      # [q, g, r]
    return (mixed / (total[..., None] + EPS)).reshape(n_q, -1)


def _mix_out(x, y, wo):
    return x + y @ wo.T


def _swiglu_part(acc, x, ln, gate_w, up_w, down_w, eps):
    """acc + down[:, part] (silu(gate[part] u) * (up[part] u)), u = rms(x;
    ln) of the layer's input `x`: `acc` starts as `x`."""
    u = _rms(x, ln, eps)
    return acc + (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def _head_block(x, norm, table, eps):
    return _rms(x, norm, eps) @ table.T


def forward(config, weights, ids):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array."""
    for key, wanted in (("sliding_window", None), ("rope_scaling", None),
                        ("use_sliding_window", False),
                        ("tie_word_embeddings", False)):
        if config.get(key, wanted) != wanted:
            raise ValueError(
                f"{key}: neither pipeedge_tpu/models/brumby.py nor this "
                "reference has it; write both before setting it")
    eps = config["rms_norm_eps"]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    hd, width = config["head_dim"], config["intermediate_size"]
    vocab = config["vocab_size"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    project = jax.jit(_project, static_argnames=("eps", "heads", "groups"))
    retention_block = jax.jit(_retention_block)
    mix_out = jax.jit(_mix_out)
    swiglu_part = jax.jit(_swiglu_part, static_argnames=("eps",))
    head_block = jax.jit(_head_block, static_argnames=("eps",))
    # the rotation's angles as HF makes them: float32 frequencies times the
    # position, cosine and sine of the float32 angle
    inv_freq = (1.0 / (float(config["rope_theta"]) ** (
        np.arange(0, hd, 2, dtype=np.float32) / hd))).astype(np.float32)
    angles = np.arange(padded, dtype=np.float32)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.concatenate([np.cos(angles)] * 2, -1))
    sin = jnp.asarray(np.concatenate([np.sin(angles)] * 2, -1))
    at = np.arange(padded)
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
            x[:length] = table[ids[row]].astype(np.float32)
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                att = root + "self_attn."
                q, k, v, gate = project(
                    x, _f32(weights, root + "input_layernorm.weight"),
                    _f32(weights, att + "q_proj.weight"),
                    _f32(weights, att + "k_proj.weight"),
                    _f32(weights, att + "v_proj.weight"),
                    _f32(weights, att + "g_proj.weight"),
                    _f32(weights, att + "q_norm.weight"),
                    _f32(weights, att + "k_norm.weight"), cos, sin, eps=eps,
                    heads=heads, groups=groups)
                lap("project", q, k, v, gate)
                # A and the decays on the host in float64 (module docstring)
                run = np.cumsum(np.asarray(gate, np.float64), axis=0).T
                mixed = []
                for start in range(0, padded, block):
                    rows = slice(start, start + block)
                    live = at[None, :] <= at[rows, None]
                    decay = np.exp(np.where(
                        live[None], run[:, rows, None] - run[:, None, :],
                        -np.inf)).astype(np.float32)
                    mixed.append(retention_block(q[rows], k, v,
                                                 jnp.asarray(decay)))
                x = mix_out(x, jnp.concatenate(mixed),
                            _f32(weights, att + "o_proj.weight"))
                del q, k, v, gate, mixed
                lap("retention", x)
                post = _f32(weights, root + "post_attention_layernorm.weight")
                gate_w = weights[root + "mlp.gate_proj.weight"]
                up_w = weights[root + "mlp.up_proj.weight"]
                down_w = weights[root + "mlp.down_proj.weight"]
                step = -(-width // FFN_PARTS)
                acc = x
                for first in range(0, width, step):
                    part = slice(first, min(first + step, width))
                    acc = jax.block_until_ready(swiglu_part(
                        acc, x, post,
                        jnp.asarray(np.asarray(gate_w[part], np.float32)),
                        jnp.asarray(np.asarray(up_w[part], np.float32)),
                        jnp.asarray(np.asarray(down_w[:, part], np.float32)),
                        eps=eps))
                x = acc
                del acc, post, gate_w, up_w, down_w
                lap("swiglu", x)
            norm = _f32(weights, "model.norm.weight")
            head = weights["lm_head.weight"]
            step = -(-vocab // VOCAB_BLOCKS)
            for first in range(0, vocab, step):
                rows = jnp.asarray(np.asarray(head[first:first + step],
                                              np.float32))
                for start in range(0, length, block):
                    stop = min(start + block, length)
                    out[row, start:stop, first:first + rows.shape[0]] = \
                        np.asarray(head_block(x[start:start + block], norm,
                                              rows, eps=eps))[:stop - start]
                del rows
            del x
            lap("head")
    print("reference brumby, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
