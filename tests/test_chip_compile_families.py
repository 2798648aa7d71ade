"""Ask the chip's compiler, without the chip: the stage programs of
`qwen3-next.longdoc-batch`, `lfm2.extract-batch` and `laguna-xs2.repo-batch`
at their real sizes.

The second half of `test_chip_compile.py` (which says what such a compile
shows and what it does not), in a file of its own so that `--dist loadfile`
gives the two halves to two workers. The described chip and the switch to
the grouped kernels are that file's fixtures.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from pipeedge_tpu.models import ShardConfig, registry, stage_cache
from test_chip_compile import (  # noqa: F401 — fixtures, found by name
    _grouped_kernels, held, mosaic_for_the_described_chip, on_chip, topo)

QWEN3_NEXT_CELL = "Qwen/Qwen3-Next-80B-A3B-Instruct@4,e0+256,v75968"


@pytest.mark.parametrize("span, last_only", [(1, False), (1024, True)])
def test_qwen3_next_stage_program_compiles_for_v5e(span, last_only, on_chip):
    """`qwen3-next.longdoc-batch` at its real size: one period (three Gated
    DeltaNet layers, one gated full-attention layer) at the published widths
    with 256 of 512 experts held, 8 rows, the 32,768 bucket; a decode step
    and one span of the prefill. The resident bytes (7.36 GB of weights,
    1.07 GB of keys and values in ONE layer, 53 MB of state in three) and
    the program's temporaries have to fit one chip's 16 GB."""
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry(QWEN3_NEXT_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 8, 32768
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    compiled = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                          on_chip((), jnp.int32), read_len=max_len,
                          last_only=last_only).compile()
    assert (_grouped_kernels(compiled) > 0) == (span == 1)
    memory = compiled.memory_analysis()
    print(f"qwen3-next {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 4096 + 6586368)
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    # keys and values in the one full layer only: four layers' would be 4.3 GB
    assert memory.argument_size_in_bytes < 7.37e9 + 1.05 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9
    # the span's temporaries as they were with the chunk's inverse a row at a
    # time (2.34 GB): the blocked inverse buys no speed with memory, the
    # cell peaks at 11.1 of 16 GB beside the benchmark's reference
    assert memory.temp_size_in_bytes < (2.35e9 if span > 1 else 0.07e9)


LFM2_CELL = "LiquidAI/LFM2-8B-A1B@12"


@pytest.mark.parametrize("span, last_only", [(1, False), (128, True)])
def test_lfm2_stage_program_compiles_for_v5e(span, last_only, on_chip):
    """`lfm2.extract-batch` at its real size: twelve blocks at the published
    widths in seven runs (two dense convolution blocks, then attention and
    routed convolution blocks), all 32 experts held, 128 rows, the 1,024
    bucket; a decode step and one span of the prefill. The resident bytes
    (8.13 GB of weights with the tied table held as embedding and as head,
    1.61 GB of keys and values in THREE layers, 19 MB of tails in nine) and
    the program's temporaries have to fit one chip's 16 GB."""
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry(LFM2_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 128, 1024
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=last_only)
    if span == 1:
        held("lfm2-step", lowered)
    compiled = lowered.compile()
    assert (_grouped_kernels(compiled) > 0) == (span == 1)
    memory = compiled.memory_analysis()
    print(f"lfm2 {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 12288 + 147456)
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    # keys and values in the three attention layers only, no leaf padded:
    # twelve layers' would be 6.4 GB
    assert memory.argument_size_in_bytes < 8.14e9 + 1.05 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9


LAGUNA_CELL = "poolside/Laguna-XS.2@5"


@pytest.mark.parametrize("span, last_only", [(1, False), (128, True)])
def test_laguna_stage_program_compiles_for_v5e(span, last_only, on_chip):
    """`laguna-xs2.repo-batch` at its real size: five blocks at the
    published widths in three runs (the dense full block of 48 query heads,
    three window blocks of 64, a routed full block), all 256 experts held,
    32 rows, the 8,192 bucket; a decode step and one span of the prefill.
    The window blocks' leaves are rings of 512 positions: 7.74 GB of
    weights, 4.29 GB of keys and values in the TWO full layers and 0.40 GB
    of rings in three (all five kept whole would be 18.5 GB with the
    weights), and the program's temporaries have to fit one chip's 16 GB."""
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    entry = registry.get_model_entry(LAGUNA_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 32, 8192
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    assert cache["k_ring"].shape == (3, rows, 512, 1024)
    assert cache["k"].shape == (2, rows, max_len, 1024)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=last_only)
    if span == 1:
        held("laguna-step", lowered)
    compiled = lowered.compile()
    assert (_grouped_kernels(compiled) > 0) == (span == 1)
    memory = compiled.memory_analysis()
    print(f"laguna {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 16384 + 3 * 512 * 8192)
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    # no leaf padded, and the rings are rings
    assert memory.argument_size_in_bytes < 7.75e9 + 1.02 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9


MELLUM_CELL = "JetBrains/Mellum2-12B-A2.5B-Instruct@8"


@pytest.mark.parametrize("rung", [1, 32])
def test_mellum_rows_step_updates_the_stage_cache_in_place(rung, on_chip):
    """`mellum2.ide-mixed`'s step at its real size (`--max-active 32
    --max-len 8704`): eight blocks at the published widths in four runs of
    two kinds, every expert held, the rung of one row and the rung of all 32
    (`decode_rows.row_rungs`). 7.59 GB of weights and 32 slots of 71.3 MB of
    rows in the TWO full layers and 25.2 MB of rings in SIX: 3.09 GB, which
    the program returns as it took them (the donated cache is the aliased
    one), with the rung's first slot and every row's position traced; each
    run's experts go through the grouped kernel; nothing of a rung's rows or
    rings is copied or converted on its way to the walk."""
    from pipeedge_tpu.models import laguna
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode_rows
    entry = registry.get_model_entry(MELLUM_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    slots, max_len = 32, 8704
    assert rung in decode_rows.row_rungs(slots)
    params = jax.eval_shape(lambda: laguna._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, slots, max_len,
        leaves=entry.family.FAMILY.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    assert cache["k_ring"].shape == (6, slots, 1024, 512)
    assert cache["k"].shape == (2, slots, max_len, 512)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    block = decode_rows.walk_block(max_len, rung)
    step = decode_rows.make_rows_step(
        entry.family.FAMILY, cfg, stage, laguna.rows_block_step, rung, block)
    compiled = step.lower(params, on_chip((slots, 1), jnp.int32), None,
                          cache, on_chip((1 + rung,), jnp.int32)).compile()
    assert _grouped_kernels(compiled) == 4      # one a run of blocks
    memory = compiled.memory_analysis()
    print(f"mellum rung {rung} of {slots}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = slots * (2 * max_len + 6 * 1024) * 4096
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    assert memory.argument_size_in_bytes < 7.60e9 + 1.01 * cache_bytes
    assert memory.temp_size_in_bytes < 1 << 29
    # (one row's head is a fused multiply and sum over the table, converted
    # inside the fusion: not a copy of it)
    moved = [dims for dims in re.findall(
        r"= \w+\[([\d,]*)\]\S* (?:copy|convert)\(", compiled.as_text())
        if str(cfg.vocab_size) not in dims.split(",")]
    largest = max([math.prod(int(n) for n in dims.split(",") if n)
                   for dims in moved], default=0)
    # a block of the walk of a rung's rows, or (one row) a layer's widest
    # weight outside the experts
    assert largest <= max(rung * block * 512, 4096 * cfg.hidden_size), (
        largest, rung, block)


KEYE_CELL = "Kwai-Keye/Keye-VL-2.0-30B-A3B@6"
MINICPM_SALA_CELL = "openbmb/MiniCPM-SALA@4"


@pytest.mark.parametrize("cell, rows, span, max_len, resident, hold", [
    (KEYE_CELL, 8, 512, 16384, 12.3e9, "keye-span-kernel"),
    (MINICPM_SALA_CELL, 2, 1024, 65536, 4.0e9, "minicpm-sala-span-kernel"),
    (QWEN3_NEXT_CELL, 8, 1024, 32768, 8.6e9, None)],
    ids=["keye", "minicpm-sala", "qwen3-next"])
def test_widest_span_program_with_the_masked_attention_kernel_compiles_for_v5e(
        cell, rows, span, max_len, resident, hold, on_chip, monkeypatch):
    """The widest span program of `keye-vl2.long-batch` (8 rows, spans of
    512, the 16,384 bucket), of `minicpm-sala.longctx-batch` (2 rows,
    spans of 1,024, the 65,536 bucket) and of `qwen3-next.longdoc-batch` (8
    rows, spans of 1,024, the 32,768 bucket: heads of 256, the span's
    queries whole, a tile of 1,024 rows) with the streaming kernel in them
    (`decoder.attend_masked` takes it on a backend that runs Mosaic, which
    the default backend here is not): a VMEM or lowering refusal shows here,
    before chip time. Argument and temporary bytes printed. `hold`: the
    line of `test_chip_compile.HELD` the lowered text is held to."""
    from pipeedge_tpu.models import decoder
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "mosaic")
    entry = registry.get_model_entry(cell)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=True)
    if hold:        # the two callers PR 46 must not move
        held(hold, lowered)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "masked_attention" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    print(f"{cell} {rows} rows, span {span} at {max_len} with the masked-"
          f"attention kernel: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    assert memory.argument_size_in_bytes < resident
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9


NEMOTRON_CELL = "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16@11,e0+128,v32768"


def _updates_and_kernels(text: str, leaf: str) -> str:
    """The scheduled entry computation of a compiled program as a string of
    `S` (a Mosaic kernel's call that gives the buffer `leaf`, `f32[5,...]`:
    the state kernel, which updates it where it lies), `U` (any other
    instruction that gives it and is or calls a `dynamic-update-slice`) and
    `K` (any other Mosaic kernel's call), in the order the chip runs them."""
    bodies = {match.group(1): match.group(2) for match in re.finditer(
        r"^%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.S | re.M)}
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    order = ""
    for line in entry.group(1).splitlines():
        _, _, made = line.partition(" = ")
        if "tpu_custom_call" in made:
            order += "S" if made.startswith("(" + leaf) else "K"
        elif made.startswith(leaf) and "parameter(" not in made:
            called = re.search(r"calls=%?([\w.\-]+)", made)
            if "dynamic-update-slice" in made + bodies.get(
                    called.group(1) if called else "", ""):
                order += "U"
    return order


@pytest.mark.parametrize("span, last_only", [(1, False), (64, True)])
def test_nemotron_h_stage_program_compiles_for_v5e(span, last_only, on_chip,
                                                   monkeypatch):
    """`nemotron3-super.reason-batch` at its real size: the period
    `MEMEMEM*EME` at the published widths in eleven runs of one block, 128
    of 512 experts held, 128 rows, the 1,024 bucket; a decode step (the
    grouped kernel over non-gated experts in a 1,024-wide latent) and one
    span of the prefill, 64 positions (the registry's: at 128 the compiler
    wants 5.5 GB of temporaries beside 12.3 GB; PERF.md, PR 47). The resident
    bytes (9.30 GB of weights, 2.76 GB of Mamba-2 state and tails in FIVE
    layers, 0.27 GB of keys and values in ONE) and the program's
    temporaries have to fit one chip's 16 GB, with no second copy of the
    state among the step's (`decode.WHOLE_IN_PLACE_BYTES`). A step's state
    goes through the in-place kernel (`ops/ssm_step.py`, which a backend
    that runs Mosaic takes; the default backend here is not one): five of
    them, no `dynamic-update-slice` and no copy of the stack left, and each
    Mamba-2 layer's kernel BEFORE the grouped kernel of the expert layer
    that follows it. The order is what PERF.md section 7, row 38 asks to be
    guarded: when the state was written by an update of the stack after the
    run (PR 47), the same compile without `_run_blocks`' fence gave
    `KKUUKKUKKUKKUKK`, the first layer's update after the next run's two
    kernels with its operands held in VMEM across them, and that program
    computed other hidden states on the chip. `y` feeds the residual, so
    data orders the kernel itself; this holds a later compiler, kernel or
    family to it. A span keeps the update and its fence (its program has no
    Mosaic kernel to slip behind)."""
    from pipeedge_tpu.models import mamba2
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    monkeypatch.setattr(mamba2, "_kernel_mode", lambda: "mosaic")
    entry = registry.get_model_entry(NEMOTRON_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 128, 1024
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count == 4648163712      # 4.648 G: 9.30 GB at 2 B
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg),
        runs=kind_runs(entry.family.FAMILY, cfg, stage)))
    assert cache["ssm_state"].shape == (5, rows, 128, 64, 128)
    assert cache["k"].shape == (1, rows, max_len, 256)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    compiled = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                          on_chip((), jnp.int32), read_len=max_len,
                          last_only=last_only).compile()
    assert cfg.prefill_chunk == 64
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (span == 1)
    memory = compiled.memory_analysis()
    print(f"nemotron-h {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 2048 + 5 * (4194304 + 122880))
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    assert memory.argument_size_in_bytes < 9.31e9 + 1.02 * cache_bytes
    # one chip's 15.75 GiB less what the runtime keeps: the span's program
    # takes 3.04 GB, the step's 0.16 GB, which no copy of a layer's 537 MB
    # of state fits into
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.6e9
    leaf = f"f32[5,{rows},128,64,128]"
    order = _updates_and_kernels(text, leaf)
    if span == 1:
        assert memory.temp_size_in_bytes < 0.4e9
        # `MEMEMEM*EME`: five state kernels and no other update of the
        # stack, five grouped kernels (one an expert layer since PR 50; a
        # pair before), and before an expert layer's kernel every earlier
        # Mamba-2 layer's state kernel
        assert order == "SKSKSKSKSK", order
        assert not re.search(re.escape(leaf) + r"\S* copy\(", text)
    else:       # a span: the chunked form, five fenced updates, no kernel
        assert order == "UUUUU", order


GRANITE_CELL = "ibm-granite/granite-4.0-h-micro"


@pytest.mark.parametrize("span, last_only", [(1, False), (64, True)])
def test_granite_hybrid_stage_program_compiles_for_v5e(span, last_only,
                                                       on_chip, monkeypatch):
    """`granite4-h-micro.summary-batch` at its real size: the whole model,
    40 blocks in nine runs (5, 9, 9, 9 and 4 Mamba-2 blocks around four
    attention blocks), 64 rows, the 1,024 bucket; a decode step and one
    span of the prefill, 64 positions. The resident bytes (6.38 GB of
    weights with the tied table ONCE, 4.83 GB of Mamba-2 state and 0.12 GB
    of tails in 36 layers, 1.07 GB of keys and values in FOUR) and the
    program's temporaries have to fit one chip's 16 GB, and a second copy
    of the state does not (`decode.WHOLE_IN_PLACE_BYTES`): a step's state
    goes through the in-place kernel (`ops/ssm_step.py`), 36 calls in block
    order each given the stack the one before handed back, no
    `dynamic-update-slice` and no copy of the stack left; a span keeps one
    fenced chain of updates a Mamba-2 run, the stack the scan's carry."""
    import time

    from pipeedge_tpu.models import mamba2
    from pipeedge_tpu.models.shard import kind_runs
    from pipeedge_tpu.parallel import decode
    monkeypatch.setattr(mamba2, "_kernel_mode", lambda: "mosaic")
    entry = registry.get_model_entry(GRANITE_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 64, 1024
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    runs = kind_runs(entry.family.FAMILY, cfg, stage)
    assert [count for _, count in runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg), runs=runs))
    assert cache["ssm_state"].shape == (36, rows, 64, 64, 128)
    assert cache["k"].shape == (4, rows, max_len, 512)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    # the head is the embedding's array: one argument of the program
    params["final"]["head"]["w"] = params["embeddings"]["wte"]
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    started = time.monotonic()
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=last_only)
    traced = time.monotonic()
    compiled = lowered.compile()
    print(f"granite-hybrid span {span}: traced and lowered in "
          f"{traced - started:.1f} s, compiled in "
          f"{time.monotonic() - traced:.1f} s")
    assert cfg.prefill_chunk == 64
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    print(f"granite-hybrid {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * (max_len * 16384 + 36 * (2097152 + 52224))
    assert memory.alias_size_in_bytes > cache_bytes     # updated in place
    # 6.38 GB of weights and the table a second time: the head's argument is
    # the embedding's array in a run, which a described shape cannot say
    assert memory.argument_size_in_bytes < 6.39e9 + 0.42e9 \
        + 1.02 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.6e9
    leaf = f"f32[36,{rows},64,64,128]"
    order = _updates_and_kernels(text, leaf)
    # no second `ssm_state`, nor a run's nine layers of it (1.2 GB)
    assert not re.search(re.escape(leaf) + r"\S* copy\(", text)
    if span == 1:
        # 0.12 GB: the unrolled step's 36 kernels, each the stack in and out
        assert memory.temp_size_in_bytes < 0.4e9
        assert order == "S" * 36, order
    else:
        # 1.57 GB where the runs' rows gathered beside the stack took 4.21:
        # a span's run carries the stack through its scan and writes a layer
        # as its block leaves it (`decode._run_blocks`), so the entry
        # computation itself updates nothing and calls no kernel
        assert memory.temp_size_in_bytes < 2.0e9
        assert order == "" and "tpu_custom_call" not in text
        assert "dynamic-update-slice" in text


BRUMBY_CELL = "manifestai/Brumby-14B-Base@10"


@pytest.mark.parametrize("span, last_only", [(1, False), (256, True)])
def test_brumby_stage_program_compiles_for_v5e(span, last_only, on_chip,
                                               monkeypatch):
    """`brumby-14b.longgen-batch` at its real size: ten of forty layers at
    the published widths in one run, both tables, 8 rows, the ONE width the
    stage binds (`max_len` 2,048: no leaf is a row a position); a decode
    step and one span of the prefill, 256 positions in two chunks of 128.
    The resident bytes (9.72 GB of weights, 2.73 GB of state and 0.02 GB of
    sums in ten layers) and the program's temporaries have to fit one chip's
    16 GB, and a second copy of the state does not help: a step's state goes
    through the in-place kernel (`ops/retention_step.py`), ten calls in
    block order each given the stack the one before handed back, no
    `dynamic-update-slice` of the stack and no copy of it; a span carries
    the stack through its scan."""
    import time

    from pipeedge_tpu.models import brumby
    from pipeedge_tpu.parallel import decode
    monkeypatch.setattr(brumby, "_kernel_mode", lambda: "mosaic")
    entry = registry.get_model_entry(BRUMBY_CELL)
    cfg = entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    rows, max_len = 8, 2048
    params = jax.eval_shape(lambda: entry.family._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    cache = jax.eval_shape(lambda: stage_cache.init_cache(
        cfg, cfg.num_hidden_layers, rows, max_len,
        leaves=entry.family.cache_leaves(cfg)))
    assert cache["pr_state"].shape == (10, rows, 8, 128, 8320)
    assert cache["pr_sum"].shape == (10, rows, 8, 8320)
    params, cache = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype), (params, cache))
    _, step = decode.make_stage_fns(entry.family.FAMILY, cfg, stage)
    started = time.monotonic()
    lowered = step.lower(params, on_chip((rows, span), jnp.int32), cache,
                         on_chip((), jnp.int32), read_len=max_len,
                         last_only=last_only)
    traced = time.monotonic()
    compiled = lowered.compile()
    print(f"brumby span {span}: traced and lowered in "
          f"{traced - started:.1f} s, compiled in "
          f"{time.monotonic() - traced:.1f} s")
    assert cfg.prefill_chunk == 256 and cfg.linear_chunk == 128
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    print(f"brumby {rows} rows, span {span}: arguments "
          f"{memory.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{memory.alias_size_in_bytes / 1e9:.2f} GB")
    cache_bytes = rows * 10 * (8 * 128 * 8320 + 8 * 8320) * 4
    assert memory.alias_size_in_bytes >= cache_bytes    # updated in place
    assert memory.argument_size_in_bytes < 9.73e9 + 1.01 * cache_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.6e9
    leaf = f"f32[10,{rows},8,128,8320]"
    order = _updates_and_kernels(text, leaf)
    assert not re.search(re.escape(leaf) + r"\S* copy\(", text)
    if span == 1:
        assert memory.temp_size_in_bytes < 0.4e9
        assert order == "S" * 10, order
    else:
        # 1.63 GB, the SwiGLU's three-part products; 4.46 GB with a KV
        # head's cell all 8 rows (`brumby.CELL_BYTES`), which did not fit
        assert memory.temp_size_in_bytes < 2.0e9
        assert order == "" and "tpu_custom_call" not in text
        assert "dynamic-update-slice" in text
