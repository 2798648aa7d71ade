"""Ask the chip's compiler, without the chip: the Pallas kernels at real
widths, and the whole ViT-Large forward.

The TPU compiler is installed beside the CPU backend and compiles for a chip
that is described, not attached. Interpret mode (the other kernel tests)
checks a kernel's arithmetic; only this checks that Mosaic accepts its block
shapes, casts and memory at the widths the main path runs. A compile that
passes here is not a chip run and says nothing about results or times.

The chip is described inside a module-scoped fixture and nowhere else, so
that every xdist worker collects the same tests and only a worker that runs
such a file loads the TPU's library. Keep every such test in this file or in
`test_chip_compile_families.py`, which takes the fixtures from here and holds
three of the five sparse families' stage programs: under `--dist loadfile` a
file is one worker's, and the two are the run's longest.
"""
import base64
import collections
import dataclasses
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from pipeedge_tpu.models import ShardConfig, layers, registry, stage_cache
from pipeedge_tpu.ops import (attention, decode_attention, fused_quant,
                              int8_matmul, quant, short_attention)
from pipeedge_tpu.parallel import expert

EDGE = (8, 197, 1024)       # the ViT-L stage edge at microbatch 8
GPT2_HEADS, GPT2_HEAD_DIM = 12, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent cache
    # but never read back; keep it off around these tests. And compile what
    # the chip runs: conftest's "highest" matmul precision is for CPU parity
    was_enabled = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    # a chip is described here and none attached: two workers (this file's
    # and `test_chip_compile_families.py`'s) may each load the TPU's library,
    # which otherwise refuses the second, and that file would skip
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:   # noqa: BLE001 — whatever says "no compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        jax.config.update("jax_default_matmul_precision", precision)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    """Shape -> the same shape placed on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return place


def _encode(bit):
    def build(on_chip):
        return (lambda x: fused_quant.fused_encode_outerdim(x, bit),
                [on_chip(EDGE, jnp.float32)])
    return build


def _decode(bit):
    def build(on_chip):
        words = quant.packed_words(int(np.prod(EDGE[1:])), bit)

        def fn(data, scale, shift):
            return fused_quant.fused_decode_outerdim(quant.QuantizedTensor(
                data=data, scale=scale, shift=shift, shape=EDGE, bit=bit))
        return fn, [on_chip((EDGE[0], words), jnp.uint32),
                    on_chip(EDGE[:1], jnp.float32),
                    on_chip(EDGE[:1], jnp.float32)]
    return build


def _matmul(m, k, n):
    def build(on_chip):
        return (lambda *ops: int8_matmul.matmul_pallas(*ops, 128),
                [on_chip((m, k), jnp.int8), on_chip((m, k // 128),
                                                    jnp.float32),
                 on_chip((k, n), jnp.int8), on_chip((n,), jnp.float32)])
    return build


def _attention(bh, seq, dim, causal):
    def build(on_chip):
        qkv = on_chip((bh, seq, dim), jnp.bfloat16)
        return (lambda q, k, v: attention.fused_attention_bhsd(
            q, k, v, causal=causal), [qkv, qkv, qkv])
    return build


def _short_attention(batch, seq, heads, head_dim, dtype=jnp.bfloat16):
    def build(on_chip):
        assert short_attention.takes(seq, heads * head_dim, head_dim,
                                     jnp.dtype(dtype).itemsize)
        qkv = on_chip((batch, seq, heads * head_dim), dtype)
        return (lambda q, k, v: short_attention.short_attention(
            q, k, v, heads, layers.einsum_core), [qkv, qkv, qkv])
    return build


def _decode_attention(variant, batch, width):
    def build(on_chip):
        h, d = GPT2_HEADS, GPT2_HEAD_DIM
        row = on_chip((batch, 1, h, d), jnp.bfloat16)
        cache = on_chip((batch, width, h, d), jnp.int8)
        meta = on_chip((batch, width, h), jnp.float32)

        def fn(q, kq, ks, kz, vq, vs, vz, kn, vn, pos):
            return decode_attention.int8_decode_attention(
                q, kq, ks, kz, vq, vs, vz, kn, vn, pos, variant=variant)
        return fn, [row, cache, meta, meta, cache, meta, meta, row, row,
                    on_chip((), jnp.int32)]
    return build


def _grouped_experts(stack, f, d, tokens, per_tok, n_experts, held,
                     act="silu"):
    """A decode step's grouped expert layer (`expert._grouped`: the sorted
    rows' gather, the kernel, the way back) over a cell's stack `[stack,
    held, ...]` of bfloat16 experts of `f` x `d`, `tokens` float32 rows each
    sent to `per_tok` of `n_experts`, at the row tile the step's call takes.
    `act` "silu": SwiGLUs; another: experts of two matrices under it."""
    def build(on_chip):
        tile = expert.expert_tile(tokens, per_tok, n_experts)
        assert tile <= expert.GROUPED_RIDGE

        def fn(rows, ups, down, layer, order, bounds, sorted_at, gates):
            return expert._grouped(
                rows, dict(ups, down=down), layer, order,
                bounds, sorted_at, gates, sorted_at % (held + 1),
                *expert.grouped_layout(tile), False, act)
        wide = on_chip((stack, held, f, d), jnp.bfloat16)
        ups = {"gate": wide, "up": wide} if act == "silu" else {"up": wide}
        return fn, [on_chip((tokens, d), jnp.float32), ups,
                    on_chip((stack, held, d, f), jnp.bfloat16),
                    on_chip((), jnp.int32),
                    on_chip((tokens * per_tok,), jnp.int32),
                    on_chip((held + 1,), jnp.int32),
                    on_chip((tokens, per_tok), jnp.int32),
                    on_chip((tokens, per_tok), jnp.float32)]
    return build


KERNELS = {
    "fused_encode_8": _encode(8),
    "fused_encode_4": _encode(4),
    "fused_decode_8": _decode(8),
    "fused_decode_4": _decode(4),
    "int8_matmul_1576x1024x4096": _matmul(1576, 1024, 4096),
    "int8_matmul_1576x4096x1024": _matmul(1576, 4096, 1024),
    "attention_16x1024x64": _attention(16, 1024, 64, causal=False),
    "attention_causal_32x4096x128": _attention(32, 4096, 128, causal=True),
    # the short core (`layers._short_core_mode` gives it these): ViT-L's
    # call, DeiT-B's, the longest row its budget holds at ViT-L's width, a
    # head of 128, float32, and rows of one tile of keys down to one row
    "short_attention_vit_l_8x197x16x64": _short_attention(8, 197, 16, 64),
    "short_attention_deit_b_8x198x12x64": _short_attention(8, 198, 12, 64),
    "short_attention_8x256x16x64": _short_attention(8, 256, 16, 64),
    "short_attention_4x197x8x128": _short_attention(4, 197, 8, 128),
    "short_attention_f32_8x197x12x64": _short_attention(
        8, 197, 12, 64, dtype=jnp.float32),
    "short_attention_8x50x16x64": _short_attention(8, 50, 16, 64),
    "short_attention_8x128x16x64": _short_attention(8, 128, 16, 64),
    "short_attention_2x1x2x64": _short_attention(2, 1, 2, 64),
    "int8_decode_attention_v1": _decode_attention(1, batch=16, width=1024),
    "int8_decode_attention_v2": _decode_attention(2, batch=16, width=256),
    # the six sparse cells' decode steps: stack, expert, rows, router, held
    "grouped_experts_lfm2": _grouped_experts(10, 1792, 2048, 128, 4, 32, 32),
    "grouped_experts_laguna": _grouped_experts(4, 512, 2048, 32, 8, 256,
                                               256),
    "grouped_experts_qwen3_next": _grouped_experts(4, 512, 2048, 8, 10, 512,
                                                   256),
    "grouped_experts_keye": _grouped_experts(6, 768, 2048, 8, 8, 128, 128),
    "grouped_experts_kimi": _grouped_experts(4, 2048, 7168, 32, 8, 384, 12),
    # experts of two matrices in the token's latent of 1,024
    "grouped_experts_nemotron": _grouped_experts(5, 2688, 1024, 128, 22, 512,
                                                 128, act="relu2"),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, on_chip):
    fn, shapes = KERNELS[name](on_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(
    name for name in KERNELS if name.startswith("grouped_experts")))
def test_a_steps_experts_are_one_kernel_and_nothing_laid_out(name, on_chip):
    """The expert layer of a step is ONE Mosaic call (until PR 50 two, the
    hidden in HBM between them), and around it nothing is as large as the
    laid rows but those rows (float32 `[rows, K]` in, `[rows, D]` out): no
    hidden `[rows, F]` and no buffer of a row's three bfloat16 parts, which
    the kernel makes in VMEM (they were XLA passes over every laid row,
    visited or not: 2.1 ms of nemotron's 28.1 ms step; PERF.md, PR 50)."""
    fn, shapes = KERNELS[name](on_chip)
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    (tokens, k), f = shapes[0].shape, shapes[1]["up"].shape[2]
    made = {(kind, tuple(int(n) for n in dims.split(",")))
            for kind, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", text)}
    laid = max(dims[0] for kind, dims in made
               if kind == "f32" and dims[1:] == (k,))
    assert laid > tokens and ("f32", (laid, f)) not in made
    assert not [dims for kind, dims in made
                if kind == "bf16" and 3 in dims and math.prod(dims) > laid]


@pytest.fixture(autouse=True)
def mosaic_for_the_described_chip(monkeypatch):
    """The default backend here is the CPU's, which keeps every expert
    layer on the tile loop and every uncached block's attention core on the
    einsums; these programs are compiled for the chip, where a step's call
    takes the grouped kernel and ViT-L's core the short kernel."""
    monkeypatch.setattr(expert, "_grouped_mode", lambda: "mosaic")
    monkeypatch.setattr(layers, "_kernel_mode", lambda: "mosaic")


def _grouped_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def program_digest(lowered) -> str:
    """sha256 of a lowered program's text with no source location in it:
    `as_text()` prints none of the program's own, and each Mosaic kernel's
    serialized body (which carries its call stack's files and lines, so a
    moved call site is another text and another key in the compile cache:
    PERF.md section 7, row 35) is decoded and printed without them."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def body(match):
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body,
                  lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


# what the programs that PR 46 must not move lowered to on its parent
# (902c6f1) for the described v5e: the five other sparse families' step
# programs at their cells' shapes and keye's and MiniCPM-SALA's widest span
# programs with the masked-attention kernel in them. A PR that means to
# change one of them replaces its line; one that does not has moved it.
# PR 50 replaced the four expert families' steps (one grouped kernel an
# expert layer where there were two and the passes between them); the
# step without experts and the two spans are 902c6f1's still
HELD = {
    "keye-step":
        "45ca775948fd5eae5c9d3d63581f70342a75d1d2a992911cad38db009bb6bc26",
    "kimi-step":
        "396ad2b254209424d7e9099982f3777c38afd58844ba2a44cb80a03270bb0ed5",
    "lfm2-step":
        "0283eb1b0f8c7cc85221583736a18a88bbfb918104911fb9557ee2f5d3dc143c",
    "laguna-step":
        "a1f55e75df3b59293def2571a07dee74953226d08692db1abec6edeaa70f9e64",
    "minicpm-sala-step":
        "d598b02c91be9c5def6972e328f1dd3e9b5d62fc5bbeb7d218bf2f221d574f7e",
    "keye-span-kernel":
        "8aa2a53a9b5635cc54a8bf8c4bc419a067b8dfcb95612cf1c934197ed94695f5",
    "minicpm-sala-span-kernel":
        "80915726e31fa0558f122b77a5c7351dde7765fe848b941f1084ea210050cbc9",
}


def held(name: str, lowered) -> None:
    digest = program_digest(lowered)
    assert digest == HELD[name], f"{name} lowers to {digest}"


VIT_LARGE = "google/vit-large-patch16-224"


def _vit_large_one_block():
    """ViT-L's parameter shapes without drawing 300M random numbers: a
    one-block model's, whose stacked blocks' leading axis the caller widens
    to the depth it compiles."""
    entry = registry.get_model_entry(VIT_LARGE)
    one_block = jax.eval_shape(lambda: entry.family.init_params(
        dataclasses.replace(entry.config, num_hidden_layers=1),
        ShardConfig(1, 4, is_first=True, is_last=True), dtype=jnp.bfloat16))
    return entry, entry.config, one_block


def test_vit_large_forward_compiles_for_v5e(on_chip):
    entry, cfg, one_block = _vit_large_one_block()
    total = registry.get_model_layers(VIT_LARGE)
    params = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), one_block)
    params["blocks"] = jax.tree_util.tree_map(
        lambda leaf: on_chip((cfg.num_hidden_layers,) + leaf.shape[1:],
                             leaf.dtype), one_block["blocks"])
    forward = registry.make_shard_fn(
        entry.family.FAMILY, cfg,
        ShardConfig(1, total, is_first=True, is_last=True))
    images = on_chip((8, cfg.num_channels, cfg.image_size, cfg.image_size),
                     jnp.bfloat16)
    compiled = jax.jit(forward).lower(params, images).compile()
    logits, = jax.tree_util.tree_leaves(compiled.out_info)
    assert logits.shape == (8, cfg.num_labels)
    # the scanned block's core is the short kernel, and nothing as large as
    # q, k or v is copied on its way in: the einsums' three transposed
    # copies (`bf16[8,197,1024]{1,2,0}`) are gone
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.findall(r"= bf16\[8,197,1024\]\{1,2,0[^}]*\} copy\(", text)
    # the GeLU behind the up product is `layers._erf_gelu`'s one divide and
    # one `exp`, fused with the product: no `erfc` expansion (two divides,
    # four selects), and nothing as wide as the MLP's hidden outside a
    # fusion that holds a product
    hidden = [line for line in text.splitlines()
              if re.search(r"= f32\[8,197,4096\]\S* [a-z]+\(", line)]
    ops = collections.Counter(
        re.search(r"\} ([a-z\-]+)\(", line).group(1) for line in hidden)
    assert "erfc" not in text
    assert ops["divide"] == 1 and ops["exponential"] == 1, ops
    assert ops["select"] == 0 and ops["convolution"] == 1, ops


def test_vit_large_train_step_compiles_for_v5e(topo):
    """`chip_smoke.py`'s `train` phase (`tools/train.py -m vit-large -t
    bfloat16 --remat -b 8 -u 4` on one chip): the loss's gradient through
    one stage of 24 rematerialised blocks. The forward's core is the short
    kernel, which has no transpose of its own: its backward is the einsums',
    so the step holds the kernel (forward and recomputed) and compiles."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pipeedge_tpu.parallel import spmd, train
    entry, cfg, one_block = _vit_large_one_block()
    blocks, n_ubatch, ubatch = cfg.num_hidden_layers, 4, 8
    mesh = spmd.make_pipeline_mesh(1, devices=topo.devices[:1])

    def placed(tree, spec, lead=()):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                lead + leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, spec)), tree)

    a_block = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype),
        one_block["blocks"])
    trainable = {
        "embed": placed(one_block["embeddings"], P()),
        "final": placed(one_block["final"], P()),
        "blocks": placed(a_block, P("stage"), (1, blocks)),
    }
    n_blocks = jax.ShapeDtypeStruct(
        (1,), jnp.int32, sharding=NamedSharding(mesh, P("stage")))
    pipe = spmd.SpmdPipeline(
        family=entry.family.FAMILY, cfg=cfg, mesh=mesh, n_stages=1,
        max_blocks=blocks, min_blocks=blocks,
        params={**trainable, "n_blocks": n_blocks}, stage_bits=(0,),
        remat=True)
    shape = (n_ubatch, ubatch, cfg.num_channels, cfg.image_size,
             cfg.image_size)
    forward = pipe._build(np.broadcast_to(np.zeros((), jnp.bfloat16), shape))

    def loss(trainable, n_blocks, images, labels):
        return train.softmax_xent(
            forward({**trainable, "n_blocks": n_blocks}, images), labels)

    fused = layers._M_CORE_BLOCKS.value(path="fused")
    einsum = layers._M_CORE_BLOCKS.value(path="einsum")
    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        trainable, n_blocks,
        jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((n_ubatch, ubatch), jnp.int32,
                             sharding=NamedSharding(mesh, P()))).compile()
    assert layers._M_CORE_BLOCKS.value(path="fused") > fused
    assert layers._M_CORE_BLOCKS.value(path="einsum") == einsum
    value, grads = compiled.out_info
    assert value.shape == ()
    assert jax.tree_util.tree_map(lambda leaf: leaf.shape, grads) \
        == jax.tree_util.tree_map(lambda leaf: leaf.shape, trainable)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("span, last_only", [(1, False), (512, True)])
def test_keye_stage_program_compiles_for_v5e(span, last_only, on_chip):
    """The benchmark's keye cell at its real size: six layers at the
    published widths, eight rows, the 16,384 bucket; a decode step and one
    span of the prefill. The resident 10.5 GB and the program's temporaries
    have to fit one chip's 16 GB."""
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry("Kwai-Keye/Keye-VL-2.0-30B-A3B@6")
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    # the shapes alone: `init_params` would draw 4.4 G values on the host
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, 8, 16384, jnp.bfloat16,
        leaves=entry.family.cache_leaves(cfg)))
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((8, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=16384,
                         last_only=last_only)
    if span == 1:
        held("keye-step", lowered)
    compiled = lowered.compile()
    assert (_grouped_kernels(compiled) > 0) == (span == 1)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 1.7e9       # the cache, in place
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9


KIMI_CELL = "moonshotai/Kimi-K2-Instruct@5,e0+12,v20480"


@pytest.mark.parametrize("rows", [32, 64])
@pytest.mark.parametrize("span, last_only", [(1, False), (128, True)])
def test_kimi_stage_program_compiles_for_v5e(span, last_only, rows, on_chip):
    """`kimi-k2.agent-batch` at its real size: the dense layer and four
    expert layers at the published widths with 12 of 384 experts held, the
    4,096 bucket; a decode step and one span of the prefill, at the cell's
    32 rows and at the 64 that ISSUE 31 sized it for (a batch of which takes
    46 s on the chip). The resident bytes (6.99 GB of weights, 3.02 GB of
    latent cache at 64 rows) and the program's temporaries have to fit one
    chip's 16 GB."""
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry(KIMI_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    # the shapes alone: `init_params` would draw 3.5 G values on the host
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, 4096,
        leaves=entry.family.cache_leaves(cfg)))
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=4096,
                         last_only=last_only)
    if (rows, span) == (32, 1):
        held("kimi-step", lowered)
    compiled = lowered.compile()
    assert (_grouped_kernels(compiled) > 0) == (span == 1)
    memory = compiled.memory_analysis()
    print(f"kimi {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * 4096 * 11520
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    # no leaf of the cache is padded: 576 values a row are what it takes
    assert memory.argument_size_in_bytes < 7.0e9 + 1.01 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9


def _gpt2_medium_shapes(on_chip):
    """(registry entry, config, one stage's parameters as shapes on the
    described chip) of gpt2-medium whole, bfloat16, from the loader."""
    entry = registry.get_model_entry("gpt2-medium")
    cfg = entry.config
    d, n_layers = cfg.hidden_size, cfg.num_hidden_layers

    def zeros(*shape):      # a checkpoint's worth of shapes, no bytes
        return np.broadcast_to(np.zeros((), np.float16), shape)

    state = {"wte.weight": zeros(cfg.vocab_size, d),
             "wpe.weight": zeros(cfg.max_position_embeddings, d),
             "ln_f.weight": zeros(d), "ln_f.bias": zeros(d)}
    for name, shape in (("ln_1", (d,)), ("ln_2", (d,)),
                        ("attn.c_attn", (d, 3 * d)), ("attn.c_proj", (d, d)),
                        ("mlp.c_fc", (d, 4 * d)), ("mlp.c_proj", (4 * d, d))):
        state[f"h.0.{name}.weight"] = zeros(*shape)
        state[f"h.0.{name}.bias"] = zeros(shape[-1])
    one_block = jax.eval_shape(lambda: entry.family.load_params(
        dataclasses.replace(cfg, num_hidden_layers=1),
        ShardConfig(1, 4, is_first=True, is_last=True), state, jnp.bfloat16))
    params = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), one_block)
    params["blocks"] = jax.tree_util.tree_map(
        lambda leaf: on_chip((n_layers,) + leaf.shape[1:], leaf.dtype),
        one_block["blocks"])
    return entry, cfg, params


def test_gpt2_medium_decode_step_keeps_cache_rows_as_rows(on_chip):
    """`gpt2-m.offline-batch`'s decode step at its real size (32 rows, 1,024
    positions, the 512 bucket, bfloat16; shapes from the loader): the chip
    keeps the cache as it is declared, a position a row of whole lanes, so
    the step writes its rows as rows and reads the window as stored. The
    three ways that was lost before (PERF.md, PR 25 and PR 32): positions
    minor-most in tiles of 128; a tile of 128 positions rewritten to store
    one; the window copied or converted whole on its way to the attention."""
    import re
    from pipeedge_tpu.parallel import decode
    rows, max_len, read_len = 32, 1024, 512
    entry, cfg, params = _gpt2_medium_shapes(on_chip)
    n_layers = cfg.num_hidden_layers
    cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: stage_cache.init_cache(
            cfg, n_layers, rows, max_len, jnp.bfloat16)))
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    compiled = step.lower(params, on_chip((rows, 1), jnp.int32), cache,
                          on_chip((), jnp.int32), read_len=read_len).compile()
    text = compiled.as_text()

    width = cfg.kv_heads * cfg.head_dim
    leaf = rf"bf16\[{n_layers},{rows},{max_len},{width}\]"
    layouts = re.findall(leaf + r"\{([\d,]+)[:}][^=]* parameter\(\d+\)"
                         r"[^\n]*op_name=\"cache\[", text)
    assert len(layouts) == 2, layouts
    for minor_to_major in layouts:      # (a) positions are axis 2
        assert minor_to_major.split(",")[0] != "2", minor_to_major
    # (b) the step handles the cache whole, a layer's window, or the rows
    assert not re.search(rf"\[(?:{n_layers}|1),{rows},128,", text)
    assert re.search(rf"bf16\[{n_layers},{rows},1,{width}\]\S* "
                     r"dynamic-update-slice\(", text)
    # (c) nothing of a window's size is copied or converted
    moved = re.findall(r"= \w+\[([\d,]*)\]\S* (?:copy|convert)\(", text)
    assert moved
    largest = max(int(np.prod([int(n) for n in dims.split(",") if n]))
                  for dims in moved)
    assert largest < rows * read_len * width, largest
    cache_bytes = 2 * n_layers * rows * max_len * width * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes


@pytest.mark.parametrize("rung", [1, 8, 32, 48])
def test_gpt2_medium_rows_step_updates_the_stage_cache_in_place(rung, on_chip):
    """The served cells' step at its real size (`--max-active 48 --max-len
    1024`, bfloat16): every rung of `decode_rows.row_rungs` compiles for the
    chip, the stage-wide cache keeps its declared layout (a position a row
    of whole lanes), the donated cache is the returned one (4.83 GB once,
    not twice), and nothing of a rung's window is copied or converted on
    its way to the walk, though the rung's first slot is traced."""
    import re
    from pipeedge_tpu.parallel import decode_rows
    slots, max_len = 48, 1024
    entry, cfg, params = _gpt2_medium_shapes(on_chip)
    n_layers, width = cfg.num_hidden_layers, cfg.kv_heads * cfg.head_dim
    assert decode_rows.row_rungs(slots) == (1, 8, 32, 48)
    cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: stage_cache.init_cache(
            cfg, n_layers, slots, max_len, jnp.bfloat16)))
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    block = decode_rows.walk_block(max_len, rung)
    step = decode_rows.make_rows_step(
        entry.family.FAMILY, cfg, stage, decode_rows.block_step_rows, rung,
        block)
    compiled = step.lower(params, on_chip((slots, 1), jnp.int32), None,
                          cache, on_chip((1 + rung,), jnp.int32)).compile()
    text = compiled.as_text()
    leaf = rf"bf16\[{n_layers},{slots},{max_len},{width}\]"
    layouts = re.findall(leaf + r"\{([\d,]+)[:}][^=]* parameter\(\d+\)",
                         text)
    assert len(layouts) >= 2, layouts
    for minor_to_major in layouts:
        assert minor_to_major.split(",")[0] != "2", minor_to_major
    cache_bytes = 2 * n_layers * slots * max_len * width * 2
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    # (the head's product with one row is a fused multiply and sum over the
    # table, converted inside the fusion: not a copy of it)
    moved = [dims for dims in re.findall(
        r"= \w+\[([\d,]*)\]\S* (?:copy|convert)\(", text)
        if str(cfg.vocab_size) not in dims.split(",")]
    largest = max([int(np.prod([int(n) for n in dims.split(",") if n]))
                   for dims in moved], default=0)
    # a block of the walk, or (one row: products of a row and a matrix) a
    # layer's widest weight
    assert largest <= max(rung * block * width,
                          cfg.hidden_size * cfg.intermediate_size), (
        largest, rung, block)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


@pytest.mark.parametrize("n_ubatch", [1024, 4])
def test_spmd_vit_large_cell_compiles_for_v5e(n_ubatch, topo):
    """`vit-l.spmd-4stage` at its real widths: four stages of six ViT-L
    blocks on the four described chips, microbatches of 8 in bfloat16; a
    round's 1,030 ticks, which have to fit a chip beside the other staged
    round (2.5 GB), and a short program of 7. The round takes the edge's
    lead (`spmd.edge_lead`): in the scheduled module the blocks' fusions
    run between `collective-permute-start` and its `-done`; the short
    program keeps the tick that waits. A chip holds its stage's
    weights as the stack it is given and once more as the per-block arrays
    the tick scan reads, never a third time among the temporaries; only the
    short program can tell, a round's two buffers of embedded images (6.6
    GB) being forty times the weights."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pipeedge_tpu.parallel import spmd
    entry, cfg, one_block = _vit_large_one_block()
    n_stages, per_stage, ubatch = 4, 6, 8
    mesh = spmd.make_pipeline_mesh(n_stages, devices=topo.devices)

    def placed(tree, spec, lead=()):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                lead + leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, spec)), tree)

    a_block = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype),
        one_block["blocks"])
    params = {
        "embed": placed(one_block["embeddings"], P()),
        "final": placed(one_block["final"], P()),
        "blocks": placed(a_block, P("stage"), (n_stages, per_stage)),
        "n_blocks": jax.ShapeDtypeStruct(
            (n_stages,), jnp.int32, sharding=NamedSharding(mesh, P("stage"))),
    }
    pipe = spmd.SpmdPipeline(
        family=entry.family.FAMILY, cfg=cfg, mesh=mesh, n_stages=n_stages,
        max_blocks=per_stage, min_blocks=per_stage, params=params,
        stage_bits=(0,) * n_stages)
    shape = (n_ubatch, ubatch, cfg.num_channels, cfg.image_size,
             cfg.image_size)
    # `_build` reads shapes only: a broadcast view stands in for 2.5 GB
    program = pipe._build(np.broadcast_to(np.zeros((), jnp.bfloat16), shape))
    compiled = program.lower(params, jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=NamedSharding(mesh, P()))).compile()
    logits, = jax.tree_util.tree_leaves(compiled.out_info)
    assert logits.shape == (n_ubatch, ubatch, cfg.num_labels)
    # the edge: a tick's schedule, from its `-start` to its `-done`
    lead = n_ubatch == 1024
    assert spmd.edge_lead(n_ubatch, pipe.n_stages) == lead
    assert pipe.n_ticks(n_ubatch) == (1030 if lead else 7)
    text = compiled.as_text()
    assert "is_scheduled=true" in text
    assert f"constant({pipe.n_ticks(n_ubatch)})" in text    # the trips
    lines = text.splitlines()
    start, = [i for i, line in enumerate(lines)
              if " collective-permute-start(" in line]
    done, = [i for i, line in enumerate(lines)
             if " collective-permute-done(" in line]
    tick = next(i for i in range(start, 0, -1) if lines[i].endswith("{"))
    end = next(i for i in range(done, len(lines)) if lines[i] == "}")
    fusions = [i for i in range(tick, end) if " fusion(" in lines[i]]
    behind = [i for i in fusions if start < i < done]
    assert len(fusions) >= 4 * per_stage
    if lead:    # the stage's blocks ride between the two
        assert len(behind) > 0.9 * len(fusions), (len(behind), len(fusions))
    else:       # they wait for `-done`
        assert not behind
    memory = compiled.memory_analysis()
    stage_bytes = per_stage * sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(a_block))
    embedded = n_ubatch * ubatch * 197 * cfg.hidden_size * 2
    assert memory.temp_size_in_bytes < 2 * embedded + 1.5 * stage_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16e9 - 2.5e9


MINICPM_SALA_CELL = "openbmb/MiniCPM-SALA@4"


@pytest.mark.parametrize("span, last_only", [(1, False), (1024, True)])
def test_minicpm_sala_stage_program_compiles_for_v5e(span, last_only,
                                                     on_chip):
    """`minicpm-sala.longctx-batch` at its real size: one period (a
    block-sparse layer, three lightning layers) at the published widths, 2
    rows, the 65,536 bucket; a decode step (pooled keys scored over the
    width, 97 blocks of 64 gathered a KV head) and one span of the prefill
    (the window under the selection's mask). The resident bytes (3.42 GB of
    weights, 0.28 GB of keys, values and pooled keys in ONE layer, 13 MB of
    state in three) and the program's temporaries have to fit one chip's
    16 GB beside the benchmark's float32 reference."""
    from pipeedge_tpu.models import ShardConfig, registry, stage_cache
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry(MINICPM_SALA_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 2, 65536
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    assert cache["k"].shape == (1, rows, max_len, 256)
    assert cache["k_pool"].shape == (1, rows, max_len // 16, 256)
    assert cache["la_state"].shape == (3, rows, 32, 128, 128)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=last_only)
    if span == 1:
        held("minicpm-sala-step", lowered)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    print(f"minicpm-sala {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e6:.0f} MB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 2112 + 3 * 2097152)
    assert memory.alias_size_in_bytes >= cache_bytes     # updated in place
    # keys and values in the one sparse layer only, no leaf padded
    assert memory.argument_size_in_bytes < 3.43e9 + 1.02 * cache_bytes
    # a span holds one KV head's lanes of the window and a chunk's scores;
    # a step its gathered slots and the pooled keys, never a window
    assert memory.temp_size_in_bytes < (1.2e9 if span > 1 else 0.1e9)
