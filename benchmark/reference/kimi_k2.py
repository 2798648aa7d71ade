"""Kimi-K2 (DeepSeek-V3's block), forward pass, plainly.

Per layer, all linears without bias, RMSNorm at `rms_norm_eps`:

1. `u = RMSNorm(x)`. `c_q = RMSNorm(W_qa u)`; `q = W_qb c_q` in heads of
   (`qk_nope_head_dim` | `qk_rope_head_dim`). `[c_kv | k_pe] = W_kva u`;
   `c_kv = RMSNorm(c_kv)`; `k_pe` is one rotary key for all heads.
   `[k_nope | v] = W_kvb c_kv` in heads of (`qk_nope_head_dim` |
   `v_head_dim`).
2. The rotary parts are turned at their position, YaRN frequencies: with
   `beta_fast` = `beta_slow` the ramp is one step wide; the frequencies
   below it stay, those above are divided by `factor`. The checkpoint keeps
   a rotary pair interleaved and the modeling code de-interleaves before it
   rotates halves: kept so here.
3. Score of head j = `(q_nope_j . k_nope_j + q_pe_j . k_pe) * s`, causal,
   softmax in float32, `W_o concat_j(sum p v_j)`, residual. `s = (nope +
   rope)**-0.5 * m**2`, `m = 0.1 * mscale_all_dim * ln(factor) + 1`; the
   cosine and sine carry `mscale / mscale_all_dim`.
4. `u = RMSNorm(x')`. A layer below `first_k_dense_replace`: `W_down
   (silu(W_gate u) * W_up u)`. The others: `g = sigmoid(W_g u)` over all
   routed experts; the `num_experts_per_tok` largest of `g + b`
   (`e_score_correction_bias`; `n_group` = `topk_group` = 1: no group is
   masked; ties to the lower expert); weights `g` of the chosen over their
   sum (+ 1e-20, as the modeling code has it) times `routed_scaling_factor`;
   `y = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)`; residual.
5. Final RMSNorm, `lm_head` (untied).

**The chip's share.** The file holds `n_routed_experts` experts a layer from
`experts_held_from`, of the `published.n_routed_experts` the router scores.
What the absent experts would add is left out, and that partial result goes
on to the next layer, as in the program.

Attention in the expanded form only: no cache, no absorbed form, no code of
the program. Sized for a chip that still holds the program's pipeline: one
tensor of a layer at a time, waited for, queries in blocks of 256, logits
block by block into a host array."""
import math
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


def frequencies(config):
    """The `qk_rope_head_dim / 2` rotary frequencies under YaRN."""
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    freqs = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    scaling = config.get("rope_scaling")
    if not scaling:
        return freqs
    original = scaling["original_max_position_embeddings"]

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    return (freqs / scaling["factor"] * ramp + freqs * (1 - ramp)).astype(
        np.float32)


def softmax_scale(config):
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    scaling = config.get("rope_scaling")
    if scaling and scaling["factor"] > 1:
        scale *= (0.1 * scaling["mscale_all_dim"]
                  * math.log(scaling["factor"]) + 1.0) ** 2
    return scale


def rotate(x, angles):
    """x [S, heads, width], pairs interleaved, by angles [S, width/2]."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def route(u, router, bias, per_tok, scaling):
    """(experts [S, k], weights [S, k]) over all the router's experts."""
    g = jax.nn.sigmoid(u @ router.T)
    _, experts = jax.lax.top_k(g + bias, per_tok)
    chosen = jnp.take_along_axis(g, experts, axis=-1)
    return experts, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scaling


def _project(x, w, angles, eps, heads, nope, rank):
    """Steps 1-2 for a whole row x [S, D]: q_nope, q_pe [S, heads, .],
    k_nope, v [S, heads, .], k_pe [S, 1, rope]."""
    length = x.shape[0]
    u = _rms(x, w["ln"], eps)
    q = (_rms(u @ w["q_a"].T, w["q_a_norm"], eps) @ w["q_b"].T).reshape(
        length, heads, -1)
    kv = u @ w["kv_a"].T
    c_kv = _rms(kv[:, :rank], w["kv_a_norm"], eps)
    k_pe = rotate(kv[:, None, rank:], angles)
    expanded = (c_kv @ w["kv_b"].T).reshape(length, heads, -1)
    return (q[..., :nope], rotate(q[..., nope:], angles),
            expanded[..., :nope], expanded[..., nope:], k_pe)


def _attention_block(q_nope, q_pe, k_nope, k_pe, v, start, scale):
    """Context [BLOCK, heads * v_dim] of the queries at [start, start +
    BLOCK) over all keys, causal."""
    n_q = q_nope.shape[0]
    at = start + jnp.arange(n_q)
    live = jnp.arange(k_nope.shape[0])[None, :] <= at[:, None]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhr,kr->hqk", q_pe, k_pe[:, 0])) * scale
    scores = jnp.where(live[None], scores, -jnp.inf)
    mixed = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return mixed.reshape(n_q, -1)


def _swiglu(u, gate_w, up_w, down_w):
    gate_w, up_w, down_w = (w.astype(jnp.float32)
                            for w in (gate_w, up_w, down_w))
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def _add_expert(delta, u, rows, weight, gate_w, up_w, down_w):
    """delta[rows] += weight * expert(u[rows]); `rows` are distinct, but for
    the spare last row that pads them."""
    return delta.at[rows].add(_swiglu(u[rows], gate_w, up_w, down_w)
                              * weight[:, None])


def _after_attention(x, mixed, o_proj, post_norm, eps):
    x = x + mixed @ o_proj.T
    return x, _rms(x, post_norm, eps)


def _head_block(x, norm, head, eps):
    return _rms(x, norm, eps) @ head.T


_ATTENTION = {
    "ln": "input_layernorm.weight",
    "q_a": "self_attn.q_a_proj.weight",
    "q_a_norm": "self_attn.q_a_layernorm.weight",
    "q_b": "self_attn.q_b_proj.weight",
    "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm": "self_attn.kv_a_layernorm.weight",
    "kv_b": "self_attn.kv_b_proj.weight",
}


def forward(config, weights, ids, record=None):
    """Logits [B, S, vocabulary] for token `ids` [B, S], float32, a host
    array. `record`, a list, is given a dict an expert layer and row: the
    `experts` [S, k] chosen and their `weights`."""
    eps = config["rms_norm_eps"]
    heads, nope = config["num_attention_heads"], config["qk_nope_head_dim"]
    rank, per_tok = config["kv_lora_rank"], config["num_experts_per_tok"]
    first = config.get("experts_held_from", 0)
    held = range(first, first + config["n_routed_experts"])
    scale = softmax_scale(config)
    ids = np.asarray(ids, np.int64)
    batch, length = ids.shape
    padded = -(-length // BLOCK) * BLOCK if length > BLOCK else length
    block = min(BLOCK, padded)
    # each step one program, compiled once
    project = jax.jit(_project, static_argnames=("eps", "heads", "nope",
                                                 "rank"))
    attend = jax.jit(_attention_block, static_argnames=("scale",))
    after_attention = jax.jit(_after_attention, static_argnames=("eps",))
    swiglu = jax.jit(_swiglu)
    router = jax.jit(route, static_argnames=("per_tok", "scaling"))
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
                             * frequencies(config)[None])
        for row in range(batch):
            x = np.zeros((padded, table.shape[1]), np.float32)
            x[:length] = table[ids[row]]
            x = jnp.asarray(x)
            for i in range(config["num_hidden_layers"]):
                root = f"model.layers.{i}."
                q_nope, q_pe, k_nope, v, k_pe = project(
                    x, {name: _f32(weights, root + key)
                        for name, key in _ATTENTION.items()},
                    angles, eps=eps, heads=heads, nope=nope, rank=rank)
                lap("project", q_nope, q_pe, k_nope, v, k_pe)
                mixed = [attend(q_nope[start:start + block],
                                q_pe[start:start + block], k_nope, k_pe, v,
                                start, scale=scale)
                         for start in range(0, padded, block)]
                x, u = after_attention(
                    x, jnp.concatenate(mixed),
                    _f32(weights, root + "self_attn.o_proj.weight"),
                    _f32(weights, root + "post_attention_layernorm.weight"),
                    eps=eps)
                del q_nope, q_pe, k_nope, v, k_pe, mixed
                lap("attention", x, u)
                if i < config["first_k_dense_replace"]:
                    x = jax.block_until_ready(
                        x + swiglu(u, *matrices(root + "mlp.")))
                    lap("dense")
                    continue
                experts, gates = router(
                    u, _f32(weights, root + "mlp.gate.weight"),
                    _f32(weights, root + "mlp.gate.e_score_correction_bias"),
                    per_tok=per_tok,
                    scaling=config["routed_scaling_factor"])
                chosen, gates = np.asarray(experts), np.asarray(gates)
                if record is not None:
                    record.append({"layer": i, "row": row,
                                   "experts": chosen[:length],
                                   "weights": gates[:length]})
                # one spare row for the padding of an expert's tokens
                delta = jnp.concatenate(
                    [swiglu(u, *matrices(root + "mlp.shared_experts.")),
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
    print("reference kimi_k2, seconds a phase: "
          + ", ".join(f"{phase} {seconds:.1f}"
                      for phase, seconds in spent.items()), file=sys.stderr)
    return out
