"""The minicpm_sala family (models/minicpm_sala.py: a block-sparse layer
that scores pooled keys and attends the blocks it keeps, beside lightning
layers that keep a state, in MiniCPM's scaled trunk) against the benchmark's
plain reference, on the CPU at `pipeedge/test-tiny-minicpm-sala`, with
seeded weights in the published key scheme; and the strided leaf of
models/stage_cache.py on its own."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs_minicpm_sala as costs, weights
from benchmark.reference import minicpm_sala as reference
from pipeedge_tpu.models import ShardConfig, minicpm_sala, registry, \
    stage_cache
from pipeedge_tpu.models.layers import rms_norm
from pipeedge_tpu.models.shard import BlockRuns, CacheLeaf, kind_runs, \
    shard_apply
from pipeedge_tpu.parallel import decode
from pipeedge_tpu.telemetry import metrics as prom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "pipeedge/test-tiny-minicpm-sala"
WHOLE = "openbmb/MiniCPM-SALA"
CELL = WHOLE + "@4"
# dense up to 32 positions, blocks of 8: a prompt of 77 crosses dense_len in
# its fifth span and ends inside a block; 100 positions are 13 blocks, of
# which a late query keeps 5 (the first, 2 local, the 2 best)
LENGTH, PROMPT, MAX_LEN = 100, 77, 128


def _config(tiny=True, **over):
    name = "minicpm-sala.json"
    with open(os.path.join(REPO, "benchmark", "configs", name)) as file:
        config = json.load(file)
    if tiny:
        with open(os.path.join(REPO, "tests", "benchmark_checks", "tiny",
                               "configs", name)) as file:
            config.update(json.load(file))
    config.update(over)
    return config


def _logits_through_the_cache(pipe, ids, prompt_len):
    data, caches = pipe._prefill(jnp.asarray(ids[:, :prompt_len], jnp.int32))
    assert data.shape[1] == 1       # the head saw the last row only
    got = [np.asarray(data[:, -1])]
    for pos in range(prompt_len, ids.shape[1]):
        data, caches = pipe.extend(ids[:, pos:pos + 1], caches, pos)
        got.append(np.asarray(data[:, 0]))
    return np.stack(got, 1), caches


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark's tiny cut, six blocks in one stage: (config, weights
    file, pipeline, ids [2, 100], reference logits, the reference's kept
    blocks a sparse layer and row)."""
    config = _config()
    path = weights.write(config, 2 ** 31 + 7, str(
        tmp_path_factory.mktemp("minicpm_sala") / "weights.npz"))
    pipe = decode.build_decode_pipeline(
        config["program_model"], None, max_len=MAX_LEN, dtype=jnp.float32,
        model_file=path)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"],
                                            size=(2, LENGTH))
    record = []
    with np.load(path) as tensors:
        wanted = reference.forward(config, tensors, ids, record=record)
    return config, path, pipe, ids, wanted, record


def _variant(path, **over):
    """A pipeline on the tiny weights with fields of the configuration
    replaced: the loader writes the trunk's factors from it."""
    entry = registry.get_model_entry(TINY)
    cfg = dataclasses.replace(entry.config, **over)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        params = minicpm_sala.load_params(cfg, stage, tensors)
    return decode.DecodePipeline(minicpm_sala.FAMILY, cfg,
                                 [(1, entry.layers)], [params],
                                 max_len=MAX_LEN)


def _gap(got, wanted):
    return float(np.abs(got - wanted).max() / (wanted.max() - wanted.min()))


# float32 program against float32 reference: they differ by the order of
# their sums (a span's window under a mask and a step's gathered blocks
# against a query block over the whole sequence, the chunked form against
# the scan, a pooled key as a product against a mean; 7.4e-8 of the logits'
# range measured); 1e-5 leaves room for another BLAS and would fail a
# bfloat16 product, a block kept that the reference did not keep or a state
# decayed a position too many a hundred times over
TOLERANCE = 1e-5


def test_spans_then_steps_through_the_cache_are_the_references_logits(tiny):
    _, _, pipe, ids, wanted, _ = tiny
    got, _ = _logits_through_the_cache(pipe, ids, PROMPT)
    assert got.shape == wanted[:, PROMPT - 1:].shape
    assert _gap(got, wanted[:, PROMPT - 1:]) < TOLERANCE


def test_a_whole_prompt_through_the_prefill_program_is_the_same(tiny):
    """`prefill=True`: no cache read, every pooled key from the call's own
    rows, the selection over the prompt alone."""
    _, _, pipe, ids, wanted, _ = tiny
    stage = pipe.stages[0]
    out, _ = stage["prefill"](stage["params"], jnp.asarray(ids, jnp.int32),
                              pipe._fresh_caches(2)[0])
    assert _gap(np.asarray(out), wanted) < TOLERANCE


def test_the_kept_blocks_are_the_references_at_every_query(tiny):
    """Layer 0 is a sparse layer and reads the embedding: its selection
    from the program's own functions (`pooled_rows` through a prefill's
    rows, `block_scores`, `select`, and `select_slots`, what a step
    gathers) against what the reference recorded for each query."""
    config, path, pipe, ids, _, record = tiny
    cfg = pipe.cfg
    sp = minicpm_sala.sparse_of(cfg)
    params = pipe.stages[0]["params"]
    block = jax.tree_util.tree_map(lambda leaf: leaf[0],
                                   params["blocks"].runs[0])
    n_blocks = -(-LENGTH // sp.block)
    t = jnp.arange(LENGTH)
    for row in range(2):
        wanted = next(entry["kept"] for entry in record
                      if entry["layer"] == 0 and entry["row"] == row)
        x = minicpm_sala.FAMILY.embed(params["embeddings"],
                                      jnp.asarray(ids[row:row + 1]), cfg)
        normed = rms_norm(block["ln_before"], x, cfg.layer_norm_eps)
        q = rms_norm(block["q_norm"], minicpm_sala._heads(
            block, "q", normed, cfg.num_attention_heads, cfg.head_dim),
            cfg.layer_norm_eps)
        k = rms_norm(block["k_norm"], minicpm_sala.lin(
            block["k"]["w"], normed).reshape(1, LENGTH, cfg.kv_heads, -1),
            cfg.layer_norm_eps)
        cache = stage_cache.LayerCache(pipe._fresh_caches(1)[0], 0)
        cache = cache._replace(stack={
            name: cache.stack[name] for name in ("k", "v", "k_pool")})
        pool, _, _ = minicpm_sala.pooled_rows(
            k.reshape(1, LENGTH, -1), cache, 0, True, sp)
        for grp in range(cfg.kv_heads):
            lanes = slice(grp * cfg.head_dim, (grp + 1) * cfg.head_dim)
            per = cfg.num_attention_heads // cfg.kv_heads
            scores = minicpm_sala.block_scores(
                q[:, :, grp * per:(grp + 1) * per], pool[..., lanes], t, sp,
                n_blocks)
            kept = np.asarray(minicpm_sala.select(scores, t, sp))[0]
            past = np.arange(LENGTH) >= sp.dense_len
            np.testing.assert_array_equal(kept[past], wanted[grp][past])
            assert (wanted[grp][past].sum(-1) == sp.slots).all()
            slots, ok = (np.asarray(x)[0] for x in
                         minicpm_sala.select_slots(scores, t, sp))
            for at in np.nonzero(past)[0]:
                assert sorted(slots[at][ok[at]]) \
                    == list(np.nonzero(wanted[grp][at])[0])


@pytest.mark.parametrize("spans", [(5, 7, 1, 3, 11, 2, 9, 1, 1, 6, 13, 4),
                                   (1,) * 40, (31, 2, 17)])
def test_the_pooled_leaf_is_the_mean_of_the_raw_keys(spans, tiny):
    """Spans of any alignment and steps: a pooled key is written by the
    call that brings its last position, from raw keys of earlier calls."""
    _, _, pipe, ids, _, _ = tiny
    caches, pos = pipe._fresh_caches(2), 0
    for span in spans:
        _, caches = pipe.extend(ids[:, pos:pos + span], caches, pos)
        pos += span
    sp = minicpm_sala.sparse_of(pipe.cfg)
    raw = np.asarray(caches[0]["k"])                    # [2, B, T, G*Dh]
    pooled = np.asarray(caches[0]["k_pool"])
    assert pooled.shape[2] == MAX_LEN // sp.stride
    done = (pos - sp.kernel) // sp.stride + 1
    wanted = np.stack([raw[:, :, j * sp.stride:j * sp.stride + sp.kernel]
                       .mean(2) for j in range(done)], 2)
    np.testing.assert_allclose(pooled[:, :, :done], wanted, atol=2e-7)
    assert not pooled[:, :, done:].any()    # nothing before it is complete


def test_chunked_one_token_and_the_references_scan_agree():
    """The state is handed over between spans of unequal length, one of
    them shorter than a chunk, then stepped."""
    rng = np.random.default_rng(5)
    b, s, h, hd = 2, 23, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
               for _ in range(3))
    cfg = registry.get_model_config(TINY)
    decay = jnp.asarray(minicpm_sala.decay_table(cfg, 2))
    wanted = np.stack([np.asarray(reference._remember(
        q[row], k[row], v[row], decay[:, 1])) for row in range(b)])
    state, got, pos = jnp.zeros((b, h, hd, hd)), [], 0
    for span in (9, 3, 8, 1, 1, 1):
        if span == 1:
            o, state = minicpm_sala.lightning_step(
                q[:, pos], k[:, pos], v[:, pos], decay, state)
            o = o[:, None]
        else:
            o, state = minicpm_sala.lightning_chunked(
                q[:, pos:pos + span], k[:, pos:pos + span],
                v[:, pos:pos + span], decay, state)
        got.append(np.asarray(o))
        pos += span
    np.testing.assert_allclose(np.concatenate(got, 1), wanted, rtol=2e-5,
                               atol=2e-5)


def test_the_decay_is_the_published_slope_of_the_published_depth():
    """s_h = 2**(-8 h / H) (1 - l / (L - 1) + 1e-5) with L the PUBLISHED
    depth, in the cut as in the whole model; powers from float64."""
    for model in (WHOLE, CELL):
        cfg = registry.get_model_config(model)
        assert cfg.published_layers == 32
        table = minicpm_sala.decay_table(cfg, 3)
        assert table.shape == (32, cfg.linear_chunk + 1)
        slope = 2.0 ** (-8.0 * np.arange(1, 33) / 32) * (1 - 3 / 31 + 1e-5)
        np.testing.assert_allclose(table[:, 1], np.exp(-slope), rtol=1e-7)
        # (the fastest head's 128th power is a denormal: its last bits alone)
        np.testing.assert_allclose(
            table[1:, 128], (np.exp(-slope).astype(np.float32).astype(
                np.float64) ** 128)[1:], rtol=1e-6)
        assert (table[:, 0] == 1).all()


def test_a_topk_of_every_block_gives_the_dense_layers_numbers(tiny):
    _, path, pipe, ids, _, _ = tiny
    sizes = pipe.cfg.sparse_attention
    every = _variant(path, sparse_attention=sizes[:3] + (64,) + sizes[4:])
    dense = _variant(path, sparse_attention=sizes[:6] + (MAX_LEN,))
    got, _ = _logits_through_the_cache(every, ids, PROMPT)
    wanted, _ = _logits_through_the_cache(dense, ids, PROMPT)
    assert _gap(got, wanted) < TOLERANCE
    # and the selection matters: the tiny model's own numbers are others
    own, _ = _logits_through_the_cache(pipe, ids, PROMPT)
    assert _gap(own, wanted) > 1e-3


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def test_a_step_reads_the_blocks_it_keeps_and_no_window(tiny):
    """A step past dense_len counts as many blocks read as kept (every slot
    holds a block by then), and its program slices nothing of the `k` and
    `v` leaves as long as the attended width: the gathered slots, the
    kernel's raw keys, and (in the `cond`'s other branch) dense_len."""
    _, _, pipe, ids, _, _ = tiny
    cfg, sp = pipe.cfg, minicpm_sala.sparse_of(pipe.cfg)
    _, caches = pipe._prefill(jnp.asarray(ids[:, :PROMPT], jnp.int32))
    before = stage_cache.read_stats(caches[0])
    _, caches = pipe.extend(ids[:, PROMPT:PROMPT + 1], caches, PROMPT)
    kept, fetched, scored, fused, dense, _, _, stepped, carried = \
        stage_cache.read_stats(caches[0]) - before
    sparse_layers = sum(kind == "minicpm4" for kind in cfg.layer_types)
    assert kept == fetched == sparse_layers * 2 * cfg.kv_heads * sp.slots
    assert scored == sparse_layers * 2 * cfg.kv_heads * (
        (PROMPT + 1 - sp.kernel) // sp.stride + 1)
    assert (dense, stepped, carried) == (0, 2 * 4, 4)
    assert fused == 0       # a one-query call keeps the einsums

    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    run = decode._make_stage_run(minicpm_sala.FAMILY, cfg, stage)
    jaxpr = jax.make_jaxpr(
        lambda p, d, c, pos: run(p, d, c, pos, prefill=False,
                                 read_len=MAX_LEN))(
        pipe.stages[0]["params"], jax.ShapeDtypeStruct((2, 1), jnp.int32),
        pipe._fresh_caches(2)[0], jax.ShapeDtypeStruct((), jnp.int32))
    leaf = (2, 2, MAX_LEN, cfg.kv_heads * cfg.head_dim)
    widths, gathers = set(), 0
    for eqn in _walk(jaxpr.jaxpr):
        shape = getattr(eqn.invars[0].aval, "shape", None) \
            if eqn.invars else None
        if shape != leaf:
            continue
        if eqn.primitive.name == "dynamic_slice":
            widths.add(eqn.params["slice_sizes"][2])
        elif eqn.primitive.name == "gather":
            assert eqn.params["slice_sizes"][2:] == (sp.block, cfg.head_dim)
            gathers += 1
    assert widths == {sp.kernel - 1, sp.dense_len}
    assert gathers == 2 * sparse_layers or gathers == 2     # k and v (a scan)


@pytest.mark.parametrize("left_out", [
    {"scale_emb": 1.0}, {"published_layers": 4}, {"dim_model_base": 32}])
def test_each_scaling_left_out_fails_the_comparison(left_out, tiny):
    """The embedding's 12, the residual's 1.4 / sqrt(PUBLISHED depth) (4
    under the root is what a cut that forgot it would compute) and the
    head's 256 / hidden."""
    _, path, _, ids, wanted, _ = tiny
    got, _ = _logits_through_the_cache(_variant(path, **left_out), ids, 96)
    assert _gap(got, wanted[:, 95:]) > 1e-3


def test_the_counters_reach_the_registry_by_phase(tiny):
    _, _, pipe, ids, _, _ = tiny

    def counter(name, phase):
        return prom.REGISTRY.counter(f"pipeedge_{name}_total", "").value(
            phase=phase)

    names = minicpm_sala.STATS
    before = {(name, phase): counter(name, phase) for name in names
              for phase in ("prefill", "decode")}
    pipe.generate(ids[:, :88], 5)
    after = {key: counter(*key) - value for key, value in before.items()}
    assert after["lightning_positions_chunked", "prefill"] == 4 * 2 * 88
    assert after["lightning_positions_stepped", "prefill"] == 0
    assert after["lightning_positions_stepped", "decode"] == 4 * 2 * 4
    assert after["lightning_state_carries", "prefill"] == 4 * 11
    assert after["sparse_dense_calls", "prefill"] == 2 * 4      # 32 / 8
    assert after["pooled_rows_written", "prefill"] == 2 * 2 * 43
    assert after["sparse_blocks_read", "decode"] \
        == after["sparse_blocks_kept", "decode"] == 2 * 2 * 2 * 5 * 4
    # a span's masked window reads every block at or before a query
    assert 0 < after["sparse_blocks_kept", "prefill"] \
        < after["sparse_blocks_read", "prefill"]
    # the CPU keeps the einsums (`decoder.attend_masked`)
    assert after["attend_fused_calls", "prefill"] == 0
    assert after["attend_fused_calls", "decode"] == 0
    gauge = prom.REGISTRY.gauge("pipeedge_cache_leaf_bytes", "")
    assert gauge.value(leaf="k_pool") == 2 * 2 * (MAX_LEN // 2) * 16 * 4
    assert gauge.value(leaf="la_state") == 4 * 2 * 4 * 8 * 8 * 4


# -- the strided leaf ----------------------------------------------------------

def test_a_strided_leaf_keeps_a_row_every_stride_positions():
    leaves = {"k": CacheLeaf((4,), jnp.float32),
              "pool": CacheLeaf((4,), jnp.float32, stride=4, reach=8)}
    cache = stage_cache.init_cache(None, 1, 2, 32, leaves=leaves)
    assert cache["pool"].shape == (1, 2, 8, 4)
    assert stage_cache.stride_names(leaves) == {"pool": (4, 8)}
    # the first row a call at `pos` can complete: 4 j + 7 >= pos
    assert [int(stage_cache.first_strided_row(pos, 4, 8))
            for pos in (0, 7, 8, 11, 12, 27)] == [0, 0, 1, 1, 2, 5]
    assert stage_cache.strided_rows(1, 4, 8) == 1
    assert stage_cache.strided_rows(9, 4, 8) == 3
    rows = {"k": jnp.ones((1, 2, 5, 4)), "pool": jnp.full((1, 2, 2, 4), 7.0)}
    out = stage_cache.write_rows(cache, rows, 12, strides={"pool": (4, 8)})
    assert np.asarray(out["pool"])[0, 0, :, 0].tolist() \
        == [0, 0, 7, 7, 0, 0, 0, 0]
    assert np.asarray(out["k"])[0, 0, :, 0].tolist() \
        == [0] * 12 + [1] * 5 + [0] * 15


def test_a_handle_knows_the_stride(tiny):
    _, _, pipe, ids, _, _ = tiny
    sig = pipe._prefix_sig()
    named = dict((leaf[0], leaf) for leaf in sig[-1])
    assert named["k_pool"][-2:] == (2, 4) and len(named["k"]) == 5
    handle = pipe.precompute_prefix(ids[0, :45])
    whole = np.asarray(pipe.generate(ids[:1, :60], 6))
    got = np.asarray(pipe.generate(ids[:1, 45:60], 6, prefix=handle))
    np.testing.assert_array_equal(got[0, 15:], whole[0, 60:])


# -- what it runs, and what it refuses by name ----------------------------------

def test_the_dense_served_path_runs_it(tiny):
    """`tools/serve.py` without pages: the wave batcher over per-request
    caches, chunks of 4 positions at any alignment, token for token with
    `generate`."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    _, _, pipe, ids, _, _ = tiny
    prompts = [ids[:1, :7], ids[1:, :61], ids[:1, 5:50]]
    batcher = ContinuousBatcher(pipe, max_active=2, chunk_tokens=4)
    for rid, prompt in enumerate(prompts):
        batcher.submit(rid, prompt, new_tokens=6)
    results = batcher.run()
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], np.asarray(pipe.generate(prompt, 6)))


def test_tools_generate_takes_the_model_and_its_cut(capsys, monkeypatch):
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate
    from pipeedge_tpu import utils
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "generate.py", "-m", TINY + "@4", "-b", "2", "--prompt-len", "40",
        "--new-tokens", "4", "--max-len", "64"])
    generate.main()
    assert "tokens" in capsys.readouterr().out


@pytest.mark.parametrize("asked", ["mesh", "sp_mesh", "ep_mesh",
                                   "tp_ep_mesh", "cache_bits", "forward",
                                   "kv_pages", "speculative", "spmd",
                                   "cut_block"])
def test_what_the_family_cannot_do_is_refused_by_name(asked):
    from jax.sharding import Mesh
    entry = registry.get_model_entry(TINY)
    _, params, stage = registry.module_shard_factory(TINY, None, 1, 24,
                                                     unroll=False)
    assert isinstance(params["blocks"], BlockRuns)
    if asked == "forward":
        with pytest.raises(NotImplementedError, match="runs of"):
            shard_apply(entry.family.FAMILY, entry.config, stage, params,
                        jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            minicpm_sala.FAMILY.sublayer({}, 0, None, entry.config)
        return
    if asked == "cut_block":
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 24)], [params], max_len=36)
        with pytest.raises(ValueError, match="whole blocks of 8"):
            pipe.extend(np.zeros((1, 4), np.int32), pipe._fresh_caches(1), 0)
        return
    if asked in ("kv_pages", "speculative"):
        pipe = decode.DecodePipeline(entry.family.FAMILY, entry.config,
                                     [(1, 24)], [params], max_len=32)
    if asked == "kv_pages":
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serve
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            serve._Service(pipe, kv_pages=4)
        return
    if asked == "speculative":
        from pipeedge_tpu.parallel.speculative import SpeculativeDecoder
        draft = decode.build_decode_pipeline("pipeedge/test-tiny-gpt2", None,
                                             max_len=32)
        for target, drafter in ((pipe, draft), (draft, pipe)):
            with pytest.raises(NotImplementedError,
                               match="minicpm_sala.*la_state"):
                SpeculativeDecoder(target, drafter)
        return
    if asked == "spmd":
        from pipeedge_tpu.parallel.spmd_decode import SpmdDecodePipeline
        mesh = Mesh(np.array(jax.devices()[:1]), ("stage",))
        with pytest.raises(NotImplementedError, match="minicpm_sala"):
            SpmdDecodePipeline(entry.family.FAMILY, entry.config, [(1, 24)],
                               [params], mesh, max_len=32)
        return
    axes = {"mesh": ("tp",), "sp_mesh": ("sp",), "ep_mesh": ("ep",),
            "tp_ep_mesh": ("tp", "ep")}
    if asked == "cache_bits":
        option, wanted = {"cache_bits": 8}, "int8 cache route"
    else:
        shape = (2,) * len(axes[asked])
        option = {asked: Mesh(np.array(jax.devices()[:2 ** len(shape)])
                              .reshape(shape), axes[asked])}
        wanted = {"mesh": "tp_cached_block_step",
                  "sp_mesh": "sp_prefill_block_step"}.get(
                      asked, "ep_cached_block_step")
    with pytest.raises(NotImplementedError, match=wanted):
        decode.DecodePipeline(entry.family.FAMILY, entry.config, [(1, 24)],
                              [params], max_len=32, **option)


def test_the_cells_cut_is_a_decoder_the_clis_take():
    assert registry.decoder_model(CELL) == CELL
    assert registry.decoder_model(WHOLE) == WHOLE
    entry = registry.get_model_entry(CELL)
    cfg = entry.config
    assert (entry.layers, cfg.num_hidden_layers, cfg.published_layers,
            cfg.vocab_size, cfg.layer_types[:4]) == (
        16, 4, 32, 73448, ("minicpm4",) + ("lightning-attn",) * 3)
    whole = registry.get_model_config(WHOLE)
    assert [i for i, kind in enumerate(whole.layer_types)
            if kind == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    # the span is a multiple of every size of the selection and the chunk
    sp = minicpm_sala.sparse_of(cfg)
    assert all(cfg.prefill_chunk % size == 0 for size in (
        sp.kernel, sp.stride, sp.block, cfg.linear_chunk))
    assert 64512 % cfg.prefill_chunk == 0 and sp.slots == 97
    assert cfg.linear_chunk == costs.CHUNK
    # every parameter of the cut, by the loader's shapes (the decay tables
    # and the two factors are the configuration's, not the checkpoint's)
    stage = ShardConfig(1, 16, is_first=True, is_last=True)
    params = jax.eval_shape(lambda: minicpm_sala._assemble(
        cfg, stage, lambda key, shape: jnp.zeros(shape), jnp.bfloat16))
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert count - 3 * 32 * 129 - 2 \
        == costs.held_parameters(_config(tiny=False)) == 1711129600
    assert kind_runs(minicpm_sala.FAMILY, cfg, stage) \
        == (("sparse", 1), ("lightning", 3))


@pytest.mark.parametrize("tiny_cut", [False, True])
def test_the_registry_holds_the_configurations_sizes(tiny_cut):
    """`sparse_config`, the scalings and the list of mixers are data of the
    configuration file; the program's registry entry holds the same."""
    config = _config(tiny=tiny_cut)
    cfg = registry.get_model_config(config["program_model"])
    sparse = config["sparse_config"]
    assert cfg.sparse_attention == tuple(sparse[key] for key in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len"))
    assert (cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base,
            cfg.rope_theta, cfg.layer_norm_eps) == (
        config["scale_emb"], config["scale_depth"], config["dim_model_base"],
        config["rope_theta"], config["rms_norm_eps"])
    assert cfg.published_layers == config["published"]["num_hidden_layers"]
    assert list(cfg.layer_types) == config["mixer_types"]
    assert cfg.num_hidden_layers == config["num_hidden_layers"]
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.kv_heads, cfg.head_dim, cfg.vocab_size) == tuple(
        config[key] for key in (
            "hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size"))


def test_the_loader_reads_the_published_keys_into_init_params_shapes(tiny):
    _, path, _, _, _, _ = tiny
    entry = registry.get_model_entry(TINY)
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        keys = set(tensors.files)
        loaded = minicpm_sala.load_params(entry.config, stage, tensors)
    drawn = minicpm_sala.init_params(entry.config, stage)
    shapes = jax.tree_util.tree_map(lambda leaf: (leaf.shape, leaf.dtype),
                                    (loaded, drawn))
    assert shapes[0] == shapes[1]
    for key in ("model.layers.0.self_attn.o_gate.weight",
                "model.layers.1.self_attn.o_norm.weight",
                "model.layers.4.self_attn.k_norm.weight",
                "model.layers.5.mlp.down_proj.weight", "lm_head.weight"):
        assert key in keys
    assert "model.layers.0.self_attn.o_norm.weight" not in keys
    assert len(keys) == 3 + 2 * 12 + 4 * 13
    assert float(loaded["embeddings"]["factor"]) == 12.0
    assert float(loaded["final"]["factor"]) == 256 / 32
    with np.load(path) as tensors:
        wider = dataclasses.replace(entry.config, intermediate_size=96)
        with pytest.raises(ValueError, match=r"mlp\.gate_proj\.weight"):
            minicpm_sala.load_params(wider, stage, tensors)


def test_the_costs_count_what_a_query_keeps():
    config = _config(tiny=False)
    assert costs.kept_positions(config, 8191) == 8192
    assert costs.kept_positions(config, 8192) == 96 * 64 + 1
    assert costs.kept_positions(config, 65023) == 6208
    assert costs.kernels_scored(config, 8191) == 0
    assert costs.kernels_scored(config, 65023) == 4063
    assert costs.kv_bytes_a_token(config) == 2112
    assert costs.state_bytes_a_row(config) == 3 * 2097152
    assert costs.sparse_mixer_params(config) == 52428800 + 256
    assert costs.lightning_mixer_params(config) == 83886080 + 256 + 4096
