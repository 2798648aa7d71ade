"""The short attention core (`ops/short_attention.py`) in interpret mode
against the einsum core, the rule by which `layers.self_attention` gives a
call to it, the counter of that choice, its gradient, and who imports it.
`tests/test_chip_compile.py` asks Mosaic whether it accepts the kernel at
real widths; the chip alone says whether it is right there (PERF.md)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.models import layers
from pipeedge_tpu.ops import short_attention as core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _einsums(q, k, v, heads):
    """`layers.einsum_core`, the one einsum core, over flat `[B, S, H * Dh]`
    operands as the kernel takes them."""
    split = (*q.shape[:2], heads, q.shape[2] // heads)
    return layers.einsum_core(q.reshape(split), k.reshape(split),
                              v.reshape(split)).reshape(q.shape)


def _qkv(rng, batch, seq, width, dtype):
    return [jnp.asarray(rng.normal(size=(batch, seq, width)), dtype)
            for _ in range(3)]


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads, head_dim", [(16, 64), (12, 64), (8, 128)])
@pytest.mark.parametrize("seq", [1, 8, 196, 197, 198, 256])
def test_the_kernel_agrees_with_the_einsum_core(seq, heads, head_dim, dtype,
                                                batch):
    """One row, a tile of sublanes, a row short of whole tiles by any
    remainder, whole tiles; two heads a slab and one; both types."""
    q, k, v = _qkv(np.random.default_rng(seq + heads), batch, seq,
                   heads * head_dim, dtype)
    got = core.short_attention(q, k, v, heads, layers.einsum_core, True)
    want = _einsums(q, k, v, heads)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # a bfloat16 context is rounded to 2^-9 of its value; float32 sums differ
    # by their order and the reciprocal by an ulp
    limit = 2 ** -8 if dtype == "bfloat16" else 2e-5
    assert np.abs(got - want).max() <= limit * max(want.max() - want.min(),
                                                   1e-6)


def _projections(rng, width, dtype="float32"):
    return {n: {"w": jnp.asarray(rng.normal(size=(width, width))
                                 * width ** -0.5, dtype),
                "b": jnp.asarray(rng.normal(size=(width,)) * 0.1, dtype)}
            for n in ("q", "k", "v")}


def _counted(path):
    return layers._M_CORE_BLOCKS.value(path=path)


# the call -> whether the kernel takes it, on a backend that runs Mosaic:
# (positions, heads, head width, dtype, then what the call carries)
CALLS = {
    "vit-l": ((197, 16, 64, "bfloat16", {}), True),
    "deit-b": ((198, 12, 64, "bfloat16", {}), True),
    "vit-b in float32": ((197, 12, 64, "float32", {}), True),
    "a head of 128": ((197, 8, 128, "bfloat16", {}), True),
    "one tile of keys": ((50, 12, 64, "bfloat16", {}), True),
    "the longest row of vit-l's width": ((256, 16, 64, "bfloat16", {}), True),
    "a padding mask": ((197, 16, 64, "bfloat16", {"mask": True}), False),
    "causal": ((197, 16, 64, "bfloat16", {"causal": True}), False),
    "a core_fn": ((197, 16, 64, "bfloat16", {"core_fn": True}), False),
    "heads of 48": ((197, 4, 48, "bfloat16", {}), False),
    "heads of 32": ((197, 4, 32, "bfloat16", {}), False),
    "gpt2-xl's 25 heads, half a slab": ((197, 25, 64, "bfloat16", {}), False),
    "a row over the budget": ((257, 16, 64, "bfloat16", {}), False),
    "vit-l at 384 pixels": ((577, 16, 64, "bfloat16", {}), False),
    "vit-l in float32, over the budget": ((197, 16, 64, "float32", {}),
                                          False),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_self_attention_takes_the_kernel_for_exactly_these_calls(
        call, monkeypatch):
    """Read off the call, no option: the kernel patched to raise proves
    which calls reach it, the counter says the same, once a traced block."""
    (seq, heads, head_dim, dtype, carried), takes = CALLS[call]
    width = heads * head_dim
    rng = np.random.default_rng(60)
    p = jax.eval_shape(lambda: _projections(rng, width, dtype))
    x = jax.ShapeDtypeStruct((2, seq, width), dtype)
    mask = jnp.ones((2, seq), jnp.int32) if carried.get("mask") else None
    core_fn = (lambda q, k, v: q) if carried.get("core_fn") else None

    def forward(p, x):
        return layers.self_attention(p, x, heads, mask=mask, core_fn=core_fn,
                                     causal=bool(carried.get("causal")))

    class Reached(Exception):
        pass

    def refuse(*args):
        raise Reached

    monkeypatch.setattr(layers, "_kernel_mode", lambda: "mosaic")
    monkeypatch.setattr(core, "short_attention", refuse)
    monkeypatch.delenv("PIPEEDGE_FUSED_ATTENTION", raising=False)
    before = _counted("fused"), _counted("einsum")
    if takes:
        with pytest.raises(Reached):
            jax.eval_shape(forward, p, x)
        moved = (1, 0)
    else:
        assert jax.eval_shape(forward, p, x).shape == (2, seq, width)
        moved = (0, 0) if core_fn else (0, 1)
    assert (_counted("fused") - before[0],
            _counted("einsum") - before[1]) == moved


def test_without_mosaic_every_call_keeps_the_einsums(monkeypatch):
    """The CPU, as it is: `_kernel_mode()` is None, ViT-L's call traces the
    einsums, and the kernel's module is not even looked at."""
    assert layers._kernel_mode() is None
    monkeypatch.setattr(core, "takes", None)
    rng = np.random.default_rng(1)
    p = jax.eval_shape(lambda: _projections(rng, 1024, "bfloat16"))
    before = _counted("einsum")
    jax.eval_shape(lambda p, x: layers.self_attention(p, x, 16), p,
                   jax.ShapeDtypeStruct((8, 197, 1024), jnp.bfloat16))
    assert _counted("einsum") - before == 1


def test_fast_numerics_keeps_the_einsums(monkeypatch):
    """Its softmax is the model type's, which the kernel's is not."""
    monkeypatch.setattr(layers, "_kernel_mode", lambda: "interpret")
    monkeypatch.setattr(layers, "_FAST_NUMERICS", True)
    assert layers._short_core_mode(197, 1024, 16, jnp.bfloat16) is None
    monkeypatch.setattr(layers, "_FAST_NUMERICS", False)
    assert layers._short_core_mode(197, 1024, 16, jnp.bfloat16) == "interpret"


def test_the_budget_is_a_function_of_rows_width_and_type():
    """ViT-L's call holds 10.2 MB of the 16 MiB; the limit the kernel asks
    for is that and the margin, a fifth of the chip's VMEM at the most."""
    assert core.vmem_bytes(197, 1024, 64, 2) == 8 * 197 * 1024 * 2 + 8 * (
        197 * 512 * 6 + 2 * 512 * 128 * 2)
    assert core.vmem_bytes(197, 1024, 64, 2) < 11 << 20
    for seq in (1, 50, 128, 197, 256, 300, 512, 1023):
        for width, head_dim in ((128, 64), (768, 64), (1024, 64),
                                (1024, 128), (5120, 128)):
            for itemsize in (2, 4):
                if core.takes(seq, width, head_dim, itemsize):
                    assert core.vmem_bytes(seq, width, head_dim, itemsize) \
                        + core.VMEM_MARGIN <= 24 << 20
    assert core.takes(256, 1024, 64, 2) and not core.takes(257, 1024, 64, 2)
    assert core.takes(197, 768, 64, 4) and not core.takes(197, 1024, 64, 4)
    assert not core.takes(197, 1600, 64, 2)     # 25 heads: half a slab
    assert not core.takes(197, 192, 48, 2) and not core.takes(197, 64, 32, 2)


@pytest.mark.parametrize("seq, heads, head_dim, dtype", [
    (197, 2, 64, "float32"), (130, 2, 64, "bfloat16"), (50, 1, 128, "float32"),
])
def test_a_traced_block_is_one_fused_sample_and_the_einsums_context(
        seq, heads, head_dim, dtype, monkeypatch):
    """`self_attention` on the kernel's path returns what the einsum path
    returns, and counts one `fused` a trace: a second call of the compiled
    program traces nothing and counts nothing."""
    rng = np.random.default_rng(3)
    width = heads * head_dim
    p = _projections(rng, width, dtype)
    x = jnp.asarray(rng.normal(size=(2, seq, width)), dtype)
    want = layers.self_attention(p, x, heads)
    monkeypatch.setattr(layers, "_kernel_mode", lambda: "interpret")
    before = _counted("fused"), _counted("einsum")
    forward = jax.jit(lambda p, x: layers.self_attention(p, x, heads))
    got = forward(p, x)
    forward(p, x)
    assert (_counted("fused") - before[0], _counted("einsum") - before[1]) \
        == (1, 0)
    assert got.dtype == want.dtype
    limit = 2 ** -7 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=limit, atol=limit)


def _vit_block_loss(heads):
    """A ViT block's first half (norm, `self_attention`, the output
    projection, the residual) into a scalar, as `tools/train.py`'s loss
    reaches it through `vit.sublayer`."""
    def loss(p, x, weight):
        normed = layers.layer_norm(p["ln"], x, 1e-6)
        ctx = layers.self_attention(p, normed, heads, tag_prefix="attn")
        out = layers.dense(p["out"], ctx) + x
        return jnp.sum(out.astype(jnp.float32) * weight)
    return loss


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("seq, heads, head_dim, dtype", [
    (197, 2, 64, "float32"), (197, 2, 64, "bfloat16"),
    (50, 1, 128, "float32"), (256, 2, 128, "bfloat16"),
])
def test_the_gradient_through_the_kernel_is_the_einsum_paths(
        seq, heads, head_dim, dtype, remat, monkeypatch):
    """A Pallas call has no transpose: the kernel entry's backward is the
    einsum core's, from q, k and v. `jax.grad` through a ViT block on the
    kernel's path gives the einsum path's gradients, for the input and
    every parameter, plain and rematerialised (`--remat`)."""
    rng = np.random.default_rng(11)
    width = heads * head_dim
    p = dict(_projections(rng, width, dtype),
             out=_projections(rng, width, dtype)["q"],
             ln={"scale": jnp.ones((width,), dtype),
                 "bias": jnp.zeros((width,), dtype)})
    x = jnp.asarray(rng.normal(size=(2, seq, width)), dtype)
    weight = jnp.asarray(rng.normal(size=(2, seq, width)), jnp.float32)

    def grads(mode):
        monkeypatch.setattr(layers, "_kernel_mode", lambda: mode)
        # a function of its own a mode: a trace is kept by the function
        loss = _vit_block_loss(heads)
        if remat:
            loss = jax.checkpoint(loss)
        before = _counted("fused")
        got = jax.grad(loss, argnums=(0, 1))(p, x, weight)
        return got, _counted("fused") - before

    want, fused = grads(None)
    assert fused == 0
    got, fused = grads("interpret")
    assert fused >= 1
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        # the forward contexts differ by a rounding of their type; the
        # backward is one function of q, k, v on both paths
        limit = 2 ** -6 if dtype == "bfloat16" else 1e-4
        assert np.abs(a - b).max() <= limit * np.abs(b).max()


def test_the_kernel_entry_differentiates_under_scan_and_checkpoint():
    """The training step's shape: blocks scanned, each rematerialised, the
    kernel inside (`parallel/spmd.py` with `remat`)."""
    seq, heads, head_dim = 50, 2, 64
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(2, seq, heads * head_dim)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(3, 3, heads * head_dim)) * 0.5 + 1,
                     jnp.float32)

    def chain(attend):
        block = jax.checkpoint(
            lambda x, w: attend(x * w[0], x * w[1], x * w[2]))
        return lambda ws, x: jnp.sum(jax.lax.scan(
            lambda carry, w: (block(carry, w), None), x, ws)[0])

    got = jax.grad(chain(lambda q, k, v: core.short_attention(
        q, k, v, heads, layers.einsum_core, True)))(ws, x)
    want = jax.grad(chain(lambda q, k, v: _einsums(q, k, v, heads)))(ws, x)
    got, want = np.asarray(got), np.asarray(want)
    # three blocks deep, a forward's last float32 bits grow with the chain
    assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max()


GPT2_STAGE = """
import sys
import jax.numpy as jnp
import numpy as np
from pipeedge_tpu.models import layers, registry
from pipeedge_tpu.parallel import decode
import tools.serve
# as on a chip: a backend that runs Mosaic, so that only the call decides
layers._kernel_mode = lambda: "interpret"
name = "pipeedge/test-tiny-gpt2"
entry = registry.get_model_entry(name)
forward, params, stage = registry.module_shard_factory(
    name, None, 1, entry.layers)
ids = np.arange(12).reshape(2, 6) % entry.config.vocab_size
assert np.isfinite(np.asarray(forward(params, jnp.asarray(ids)))).all()
pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                             [(1, entry.layers)], [params], max_len=16)
assert np.asarray(pipe.generate(ids, new_tokens=4)).shape == (2, 10)
assert layers._M_CORE_BLOCKS.value(path="einsum") >= 1
assert layers._M_CORE_BLOCKS.value(path="fused") == 0
assert "pipeedge_tpu.ops.short_attention" not in sys.modules, "imported"
print("kept out")
"""


def test_a_gpt2_stage_and_the_server_never_import_the_kernel(tmp_path):
    """The served cells' processes load what the parent's load: building and
    running a GPT-2 stage (`causal=True`: the forward, a prefill and decode
    steps) and importing `tools.serve` leave the kernel's module out of
    `sys.modules`, even where the backend could run it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run([sys.executable, "-c", GPT2_STAGE], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert done.stdout.strip().endswith("kept out")
