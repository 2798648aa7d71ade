"""MiniCPM-SALA, forward pass, plainly: from the equations written down in
`configs/minicpm-sala.json` (`assumed`), a query at a time where the model
chooses, a position at a time where it remembers; no cache, no chunked form,
no mask shared between queries, no code of the program.

All linears without bias; `rms(x, w) = x * rsqrt(mean(x^2) + eps) * w`.

Trunk (MiniCPM's): `h = embed[ids] * scale_emb`; a layer `h += r
Mixer(rms(h))`, then `h += r FFN(rms(h))`, `r = scale_depth /
sqrt(published.num_hidden_layers)`, FFN `down(silu(gate u) * up u)`; logits
`lm_head(rms(h) / (hidden_size / dim_model_base))`.

1. **`lightning-attn`.** q, k, v of `lightning_nh` heads of
   `lightning_head_dim`; q, k normed a head (`q_norm`, `k_norm`), then turned
   (`rotate_half`, every lane, `inv_freq = theta**(-2i / Dh)`, absolute
   position). A head's state `S` [Dh, Dh] starts at 0; a position: `S <-
   lambda_h S + k v^T`, `o = q S / sqrt(Dh)`. `lambda_h = exp(-s_h)`, `s_h =
   2**(-8 h / H) (1 - l / (L - 1) + 1e-5)`, `h` = 1..H, `l` the layer's
   index, `L` the published depth; computed in float64 and rounded once.
   Then `o_proj(rms(o, o_norm) * sigmoid(o_gate u))`, the norm over the
   joined heads.
2. **`minicpm4`** (`sparse_config`: kernel_size K, kernel_stride s,
   block_size b, topk, init_blocks, window_size, dense_len). q of
   `num_attention_heads`, k, v of `num_key_value_heads` heads of `head_dim`;
   q, k normed a head; no rotation; scores over `sqrt(Dh)`; query head `h`
   reads KV head `h // (H / G)`. Query `t < dense_len`: softmax over every
   position at or before `t`. Otherwise, a KV head `g`:
   `Kbar_j = mean(k[s j : s j + K])` for the `j` with `s j + K - 1 <= t`;
   `p_h = softmax_j(q_h . Kbar_j / sqrt(Dh))`; `s_g(j) = sum` of `p_h` over
   the heads that read `g` (0 for any other `j`); block `m` (positions `[b
   m, b m + b)`) scores `max s_g(j)` over the kernels whose positions
   overlap it; kept are the blocks below `init_blocks`, the `window_size / b`
   blocks that end with the query's own block, and of the remaining blocks
   at or before it the `topk` with the largest score (ties to the lower
   block, all of them where they are fewer); softmax over the kept blocks'
   positions at or before `t`. Then `o_proj(o * sigmoid(o_gate u))`.

Sized for a chip that still holds the program's weights: one row at a time,
a tensor of the file at a time (float16 as stored, widened where it is
used), the sparse layer a KV head and `BLOCK` queries at a time, the FFN and
the head in blocks of rows, logits into a host array."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128         # queries of one KV head on the device at a time
ROWS = 4096         # rows of an FFN at a time
HEAD_ROWS = 256     # rows of logits at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _times(x, w):
    """x [., in] through an `nn.Linear` kernel [out, in] as stored."""
    return x @ w.astype(jnp.float32).T


def _project(u, w, norm, eps, heads):
    """u [S, D] -> [S, heads, Dh], normed a head where `norm` is given."""
    out = _times(u, w).reshape(u.shape[0], heads, -1)
    return out if norm is None else _rms(out, norm, eps)


def _turn(x, angles):
    """x [S, heads, Dh] turned by `angles` [S, Dh / 2], halves layout."""
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _remember(q, k, v, decay):
    """o [S, H, Dh] of the recurrence, a position at a time from a zero
    state; `decay` [H]. Sums on the vector unit, exact in float32."""
    def step(state, xs):
        q_t, k_t, v_t = xs
        state = decay[:, None, None] * state \
            + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], 1)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v))[1]


def _gated_out(x, o, u, gate_w, out_w, norm, eps, r):
    """x + r o_proj(o' * sigmoid(o_gate u)); o' the joined heads, normed
    where `norm` is given."""
    o = o.reshape(o.shape[0], -1)
    if norm is not None:
        o = _rms(o, norm, eps)
    return x + r * _times(o * jax.nn.sigmoid(_times(u, gate_w)), out_w)


def _pooled(k, kernel, stride):
    """Kbar [J, Dh] of one KV head's keys k [S, Dh]: every whole kernel."""
    count = (k.shape[0] - kernel) // stride + 1
    members = np.arange(count)[:, None] * stride + np.arange(kernel)[None]
    return jnp.mean(k[members], axis=1)


def _choose(q, pooled, start, sizes, n_blocks):
    """Which blocks the queries q [Q, r, Dh] of ONE KV head at positions
    `start + arange(Q)` keep: [Q, n_blocks] bool (step 2's selection)."""
    kernel, stride, block, topk, init_blocks, window, dense_len = sizes
    t = start + jnp.arange(q.shape[0])
    held = pooled.shape[0]
    done = (np.arange(held) * stride + kernel - 1)[None] <= t[:, None]
    logit = jnp.einsum("qrd,jd->rqj", q, pooled) / np.sqrt(q.shape[-1])
    share = jnp.where(done, jax.nn.softmax(
        jnp.where(done, logit, -jnp.inf), -1), 0.0).sum(0)          # [Q, J]
    # the kernels whose positions overlap block m, a row of `around`
    wide = (block + kernel) // stride - 1
    around = np.arange(n_blocks)[:, None] * (block // stride) \
        - (kernel // stride - 1) + np.arange(wide)[None]
    inside = (around >= 0) & (around < held)
    score = jnp.max(jnp.where(
        inside, share[:, np.clip(around, 0, held - 1)], 0.0), -1)   # [Q, M]
    m = jnp.arange(n_blocks)[None]
    own = (t // block)[:, None]
    first = m < init_blocks
    local = (m > own - window // block) & (m <= own)
    others = (m <= own) & ~first & ~local
    order = jnp.argsort(jnp.where(others, -score, jnp.inf), -1, stable=True)
    best = others & (jnp.argsort(order, -1) < topk)
    kept = (first & (m <= own)) | local | best
    return jnp.where((t < dense_len)[:, None], m <= own, kept)


def _attend_kept(q, k, v, kept, start, block):
    """Softmax attention of q [Q, r, Dh] (one KV head's queries) over the
    positions of the blocks `kept` [Q, M] at or before each query."""
    at = jnp.arange(k.shape[0])
    live = kept[:, at // block] \
        & (at[None] <= (start + jnp.arange(q.shape[0]))[:, None])
    logit = jnp.einsum("qrd,kd->rqk", q, k) / np.sqrt(q.shape[-1])
    weight = jax.nn.softmax(jnp.where(live[None], logit, -jnp.inf), -1)
    return jnp.einsum("rqk,kd->qrd", weight, v)


def _ffn(x, gate_w, up_w, down_w, norm, eps, r):
    u = _rms(x, norm, eps)
    return x + r * _times(jax.nn.silu(_times(u, gate_w)) * _times(u, up_w),
                          down_w)


def _logits(x, norm, head, eps, shrink):
    return _times(_rms(x, norm, eps) / shrink, head)


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict a `minicpm4` layer and row:
    `kept` [G, S, blocks] bool, the blocks each query keeps a KV head."""
    eps = config["rms_norm_eps"]
    depth = config["published"]["num_hidden_layers"]
    r = config["scale_depth"] / np.sqrt(depth)
    sparse = config["sparse_config"]
    sizes = tuple(sparse[key] for key in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len"))
    kernel, stride, block = sizes[:3]
    heads, groups = config["num_attention_heads"], \
        config["num_key_value_heads"]
    l_heads, l_dim = config["lightning_nh"], config["lightning_head_dim"]
    inv_freq = 1.0 / (float(config["rope_theta"]) ** (
        np.arange(0, l_dim, 2, dtype=np.float32) / l_dim))
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    q_block = min(BLOCK, padded)
    n_blocks = -(-padded // block)
    project = jax.jit(_project, static_argnames=("eps", "heads"))
    norm_rows = jax.jit(_rms, static_argnames=("eps",))
    turn = jax.jit(_turn)
    remember = jax.jit(_remember)
    gated_out = jax.jit(_gated_out, static_argnames=("eps", "r"))
    pooled_keys = jax.jit(_pooled, static_argnames=("kernel", "stride"))
    choose = jax.jit(_choose, static_argnames=("sizes", "n_blocks"))
    attend_kept = jax.jit(_attend_kept, static_argnames=("block",))
    ffn = jax.jit(_ffn, static_argnames=("eps", "r"))
    logits = jax.jit(_logits, static_argnames=("eps", "shrink"))
    out = np.empty((batch, length, config["vocab_size"]), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    def stored(key):        # float16 as the file has it; widened in use
        return jnp.asarray(np.asarray(weights[key]))

    def scale(key):
        return jnp.asarray(np.asarray(weights[key], np.float32))

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        angles = jnp.asarray(np.arange(padded, dtype=np.float32)[:, None]
                             * inv_freq[None])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]].astype(np.float32) \
                * config["scale_emb"]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                att = root + "self_attn."
                u = norm_rows(x, scale(root + "input_layernorm.weight"),
                              eps=eps)
                if config["mixer_types"][i] == "minicpm4":
                    q = project(u, stored(att + "q_proj.weight"),
                                scale(att + "q_norm.weight"), eps=eps,
                                heads=heads)
                    k = project(u, stored(att + "k_proj.weight"),
                                scale(att + "k_norm.weight"), eps=eps,
                                heads=groups)
                    v = project(u, stored(att + "v_proj.weight"), None,
                                eps=eps, heads=groups)
                    lap("project", q, k, v)
                    q = q.reshape(padded, groups, heads // groups, -1)
                    mixed, kept_all = [], []
                    for g in range(groups):
                        k_g, v_g = k[:, g], v[:, g]
                        pooled = pooled_keys(k_g, kernel=kernel,
                                             stride=stride) \
                            if padded >= kernel else jnp.zeros(
                                (1, k_g.shape[1]), jnp.float32)
                        ctx, kept_g = [], []
                        for start in range(0, padded, q_block):
                            q_b = q[start:start + q_block, g]
                            kept = choose(q_b, pooled, start, sizes=sizes,
                                          n_blocks=n_blocks)
                            ctx.append(attend_kept(q_b, k_g, v_g, kept,
                                                   start, block=block))
                            if record is not None:
                                kept_g.append(np.asarray(kept))
                        mixed.append(jnp.concatenate(ctx))
                        if record is not None:
                            kept_all.append(np.concatenate(kept_g)[:length])
                    if record is not None:
                        record.append({"layer": i, "row": row,
                                       "kept": np.stack(kept_all)})
                    x = gated_out(x, jnp.stack(mixed, 1), u,
                                  stored(att + "o_gate.weight"),
                                  stored(att + "o_proj.weight"), None,
                                  eps=eps, r=r)
                    del q, k, v, mixed, u
                    lap("sparse", x)
                else:
                    slope = 2.0 ** (-8.0 * np.arange(1, l_heads + 1) / l_heads) \
                        * (1.0 - i / max(depth - 1, 1) + 1e-5)
                    decay = jnp.asarray(np.exp(-slope).astype(np.float32))
                    q = turn(project(u, stored(att + "q_proj.weight"),
                                     scale(att + "q_norm.weight"), eps=eps,
                                     heads=l_heads), angles)
                    k = turn(project(u, stored(att + "k_proj.weight"),
                                     scale(att + "k_norm.weight"), eps=eps,
                                     heads=config["lightning_nkv"]), angles)
                    v = project(u, stored(att + "v_proj.weight"), None,
                                eps=eps, heads=config["lightning_nkv"])
                    lap("project", q, k, v)
                    o = remember(q, k, v, decay) / np.sqrt(l_dim)
                    del q, k, v
                    lap("recurrence", o)
                    x = gated_out(x, o, u, stored(att + "o_gate.weight"),
                                  stored(att + "o_proj.weight"),
                                  scale(att + "o_norm.weight"), eps=eps, r=r)
                    del o, u
                    lap("project", x)
                mats = [stored(f"{root}mlp.{name}_proj.weight")
                        for name in ("gate", "up", "down")]
                post = scale(root + "post_attention_layernorm.weight")
                x = jnp.concatenate([
                    jax.block_until_ready(ffn(
                        x[start:start + ROWS], *mats, post, eps=eps, r=r))
                    for start in range(0, padded, ROWS)])
                del mats
                lap("ffn", x)
            norm = scale("model.norm.weight")
            head = stored("lm_head.weight")
            shrink = config["hidden_size"] / config["dim_model_base"]
            rows = min(HEAD_ROWS, padded)
            for start in range(0, length, rows):
                stop = min(start + rows, length)
                out[row, start:stop] = np.asarray(logits(
                    x[start:start + rows], norm, head, eps=eps,
                    shrink=shrink))[:stop - start]
            del head, x
            lap("head")
    print("reference minicpm_sala, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
