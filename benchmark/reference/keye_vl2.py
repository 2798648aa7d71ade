"""Keye-VL-2.0's language model, forward pass, plainly.

A Qwen3-MoE decoder (`model_type` KeyeVL2's config repeats it key for key)
whose attention reads a learned selection of its keys, after DeepSeek-V3.2's
lightning indexer, which the catalog's description names. Per layer, with
all linears without bias and RMSNorm at `rms_norm_eps`:

1. `h = RMSNorm(x)`; `q, k, v = W_q h, W_k h, W_v h` in heads of `head_dim`;
   each head of q and of k RMS-normed (`q_norm`, `k_norm`).
2. M-RoPE, half-split rotation: of a head's `head_dim / 2` frequencies the
   first `mrope_section[0]` turn with the temporal position, the next with
   the height, the last with the width; text has all three equal.
3. Indexer: `qI = WI_q h` in `indexer_num_heads` heads of
   `indexer_head_dim`; `kI = LayerNorm(WI_k h)`, one head; both rotated at
   their temporal position over the whole head; `w = WI_w h *
   heads**-0.5 * head_dim**-0.5`. `I[t, s] = sum_j w[t, j] relu(qI[t, j] .
   kI[s])` for `s <= t`. A query keeps the `topk` positions of largest `I`
   (all while `t < topk`; ties to the lower position).
4. Grouped-query softmax attention over the kept positions, `W_o`, residual.
5. `u = RMSNorm(x')`; `r = softmax(W_r u)`; the `num_experts_per_tok`
   largest, renormalised to sum to one (`norm_topk_prob`); each chosen
   expert adds `c_e W_down (silu(W_gate u) * W_up u)`; residual.
6. Final RMSNorm, `lm_head` (untied).

Assumed, and said so in the configuration's file: the per-head q/k norms,
the indexer's rotation over its whole head and its LayerNorm's epsilon
(`rms_norm_eps`), selection by token and not by chunk. Left out: the vision
tower.

No cache and no code of the program. Sized for a chip that still holds the
program's pipeline: one layer's attention weights at a time, experts read
from the file one at a time and waited for every few, queries in blocks of
256, and the logits assembled block by block into a host array (10 GB for
16,384 positions of this vocabulary), never more than one block on the
device."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256         # queries (and rows of logits) on the device at a time
_FENCE = 8          # experts dispatched before the host waits


def _f32(weights, key):
    """A tensor of the file as float32 on the device, widened on the host
    (no program to compile for a mere conversion)."""
    return jnp.asarray(np.asarray(weights[key], np.float32))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate(x, angles):
    """Half-split rotation of x [S, heads, width] by angles [S, width/2]."""
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mrope_angles(positions, width, theta, section):
    """[S, width/2]: frequency f of a head turns with the position row that
    `section` gives it. `positions` [3, S]."""
    inv_freq = 1.0 / (theta ** (np.arange(0, width, 2, dtype=np.float32)
                                / width))
    row = np.repeat(np.arange(3), section)
    return jnp.asarray(positions, jnp.float32)[row].T * inv_freq[None]


def keep_mask(score, live, topk):
    """The `topk` best of `score` [Q, K] among `live`, as a mask (all of the
    live where there are no more); among equal scores the lower position
    first. By a full sort: the `topk`-th largest value, everything above
    it, and of those equal to it the first few."""
    if score.shape[-1] <= topk:
        return live
    score = jnp.where(live, score, -jnp.inf)
    kth = jnp.sort(score, axis=-1)[:, -topk][:, None]
    above = score > kth
    level = (score == kth) & live
    spare = topk - above.sum(-1, keepdims=True)
    return (above & live) | (level & (jnp.cumsum(level, axis=-1) <= spare))


def route(u, router, per_tok):
    """(experts [S, k], gates [S, k]): softmax over all experts, the k
    largest, renormalised."""
    probs = jax.nn.softmax(u @ router.T, axis=-1)
    gates, experts = jax.lax.top_k(probs, per_tok)
    return experts, gates / gates.sum(-1, keepdims=True)


# the layer's attention tensors, as `_project` names them
_ATTENTION = {
    "ln": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "q_norm": "self_attn.q_norm.weight",
    "k_norm": "self_attn.k_norm.weight",
    "iq": "self_attn.indexer.wq.weight", "ik": "self_attn.indexer.wk.weight",
    "iw": "self_attn.indexer.weights_proj.weight",
    "ik_scale": "self_attn.indexer.k_norm.weight",
    "ik_bias": "self_attn.indexer.k_norm.bias",
}


def _project(x, w, angles, i_angles, eps, heads, kv_heads, i_heads):
    """Steps 1-3 for a whole row x [S, D]: q [S, heads, width], k, v,
    the indexer's qI [S, i_heads, i_width], kI [S, i_width] and w [S,
    i_heads]. `w`: the layer's tensors under `_ATTENTION`'s names."""
    length = x.shape[0]
    h = _rms(x, w["ln"], eps)
    q = (h @ w["q"].T).reshape(length, heads, -1)
    k = (h @ w["k"].T).reshape(length, kv_heads, -1)
    v = (h @ w["v"].T).reshape(length, kv_heads, -1)
    q = rotate(_rms(q, w["q_norm"], eps), angles)
    k = rotate(_rms(k, w["k_norm"], eps), angles)
    qi = rotate((h @ w["iq"].T).reshape(length, i_heads, -1), i_angles)
    ki = h @ w["ik"].T
    mean = ki.mean(-1, keepdims=True)
    var = ((ki - mean) ** 2).mean(-1, keepdims=True)
    ki = (ki - mean) / jnp.sqrt(var + eps) * w["ik_scale"] + w["ik_bias"]
    ki = rotate(ki[:, None], i_angles)[:, 0]
    wi = h @ w["iw"].T * (i_heads ** -0.5 * qi.shape[-1] ** -0.5)
    return q, k, v, qi, ki, wi


def _attention_block(q, k, v, qi, wi, ki, start, topk):
    """Context [BLOCK, heads * width] of the queries at [start, start +
    BLOCK) over all S keys, causal, through the selection."""
    n_q, heads, width = q.shape
    groups = k.shape[1]
    at = start + jnp.arange(n_q)
    live = jnp.arange(k.shape[0])[None, :] <= at[:, None]
    score = jnp.einsum("qjd,kd->qjk", qi, ki)
    score = jnp.sum(jax.nn.relu(score) * wi[..., None], axis=1)
    score = jnp.where(score == 0.0, 0.0, score)     # no negative zero
    keep = keep_mask(score, live, topk)
    qg = q.reshape(n_q, groups, heads // groups, width)
    logits = jnp.einsum("qgrd,kgd->grqk", qg, k) / np.sqrt(width)
    logits = jnp.where(keep[None, None], logits, -jnp.inf)
    mixed = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(logits, -1), v)
    return mixed.reshape(n_q, heads * width), keep


def _expert(u, gate_w, up_w, down_w):
    """One expert on its tokens; its matrices may come as the file has them
    (float16: half the bytes to send) and are widened here."""
    gate_w, up_w, down_w = (w.astype(jnp.float32)
                            for w in (gate_w, up_w, down_w))
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def _add_expert(delta, u, rows, gate, gate_w, up_w, down_w):
    """delta[rows] += gate * expert(u[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return delta.at[rows].add(_expert(u[rows], gate_w, up_w, down_w)
                              * gate[:, None])


def _after_attention(x, mixed, o_proj, post_norm, router, eps, per_tok):
    """Step 4's projection and residual, then step 5's norm and routing:
    (x', u, experts [S, k], gates [S, k])."""
    x = x + mixed @ o_proj.T
    u = _rms(x, post_norm, eps)
    experts, gates = route(u, router, per_tok)
    return x, u, experts, gates


def _head_block(x, norm, head, eps):
    return _rms(x, norm, eps) @ head.T


def forward(config, weights, ids, positions=None, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `positions` [3, S] places the tokens for the M-RoPE (text: all
    three the index). `record`, a list, is given a dict a layer and row:
    the `kept` mask [S, S] and the `experts` [S, k] chosen."""
    eps = config["rms_norm_eps"]
    heads, kv_heads = config["num_attention_heads"], \
        config["num_key_value_heads"]
    width, theta = config["head_dim"], float(config["rope_theta"])
    sa = config["sa_config"]
    i_heads, i_width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    per_tok = config["num_experts_per_tok"]
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    if positions is None:
        positions = np.broadcast_to(np.arange(padded), (3, padded))
    # each step one program, compiled once: op by op the chip would compile
    # some hundred small programs at a second or two each
    project = jax.jit(_project, static_argnames=(
        "eps", "heads", "kv_heads", "i_heads"))
    attend = jax.jit(_attention_block, static_argnames=("topk",))
    after_attention = jax.jit(_after_attention,
                              static_argnames=("eps", "per_tok"))
    add_expert = jax.jit(_add_expert, donate_argnums=0)
    head_block = jax.jit(_head_block, static_argnames=("eps",))
    out = np.empty((batch, length, config["vocab_size"]), np.float32)
    spent, mark = {}, [time.monotonic()]

    def lap(phase, *waited_for):
        """Charge the time since the last lap to `phase`."""
        jax.block_until_ready(waited_for)
        now = time.monotonic()
        spent[phase] = spent.get(phase, 0.0) + now - mark[0]
        mark[0] = now

    with jax.default_matmul_precision("highest"):
        table = np.asarray(weights["model.embed_tokens.weight"])
        angles = mrope_angles(positions, width, theta,
                              config["rope_scaling"]["mrope_section"])
        i_angles = mrope_angles(positions, i_width, theta,
                                [i_width // 2, 0, 0])   # temporal only
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                q, k, v, qi, ki, wi = project(
                    x, {name: _f32(weights, root + key)
                        for name, key in _ATTENTION.items()},
                    angles, i_angles, eps=eps, heads=heads,
                    kv_heads=kv_heads, i_heads=i_heads)
                lap("project", q, k, v, qi, ki, wi)
                mixed, kept = [], []
                for start in range(0, padded, block):
                    part = slice(start, start + block)
                    ctx, keep = attend(q[part], k, v, qi[part], wi[part], ki,
                                       start, topk=sa["topk"])
                    mixed.append(ctx)
                    if record is not None:
                        kept.append(np.asarray(keep))
                x, u, experts, gates = after_attention(
                    x, jnp.concatenate(mixed),
                    _f32(weights, root + "self_attn.o_proj.weight"),
                    _f32(weights, root + "post_attention_layernorm.weight"),
                    _f32(weights, root + "mlp.gate.weight"), eps=eps,
                    per_tok=per_tok)
                del q, k, v, qi, ki, mixed
                chosen, gates = np.asarray(experts), np.asarray(gates)
                lap("attention")
                if record is not None:
                    record.append({"layer": i, "row": row,
                                   "kept": np.concatenate(kept)[:length,
                                                                :length],
                                   "experts": chosen[:length]})
                # one spare row for the padding of an expert's tokens
                delta = jnp.zeros((padded + 1, x.shape[1]), jnp.float32)
                u_spare = jnp.concatenate([u, jnp.zeros_like(u[:1])])
                for e in range(config["num_experts"]):
                    tokens, slot = np.nonzero(chosen == e)
                    if not len(tokens):
                        continue
                    pad = -len(tokens) % 256 if padded > 256 else 0
                    rows = np.concatenate(
                        [tokens, np.full(pad, padded)]).astype(np.int32)
                    gate = np.concatenate(
                        [gates[tokens, slot], np.zeros(pad, np.float32)])
                    base = f"{root}mlp.experts.{e}."
                    delta = add_expert(
                        delta, u_spare, rows, gate,
                        *(np.asarray(weights[base + name + "_proj.weight"])
                          for name in ("gate", "up", "down")))
                    if e % _FENCE == _FENCE - 1:
                        jax.block_until_ready(delta)
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
    print("reference keye_vl2, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
