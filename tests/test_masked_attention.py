"""The streaming masked-attention kernel (`ops/masked_attention.py`) in
interpret mode against the einsums it replaces (`decoder.softmax_over`), and
the seam that picks between them (`decoder.attend_masked`).

The CPU backend keeps every call on the einsums (`decoder._fused_mode` is
None here); the tests put "interpret" there, as `tests/test_grouped_experts.py`
does with `expert._grouped_mode`. `tests/test_chip_compile_families.py` puts
"mosaic" there to compile the two cells' widest span programs for a described
v5e.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.models import decoder, keye, registry
from pipeedge_tpu.ops import masked_attention


def _case(rows, n_q, heads, keys, *, block=0, dead=(), blind=None, seed=0,
          scale=1.0, lanes=128, share=0.3):
    """Queries [B, Q, r, `lanes`], parts of `keys` keys and their masks: a
    key is kept with probability `share` (`block`: a block of that many at a
    time; 1.0: qwen3_next's mask, causal and nothing else),
    never inside `dead` (part, from, to), never in part `blind[0]` for the
    queries of row 0 from `blind[1]` on; the LAST part is causal from its
    start (a span's own rows), so every query keeps a key."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(rows, n_q, heads, lanes)) * scale
    ks = [rng.normal(size=(rows, n, lanes)) for n in keys]
    vs = [rng.normal(size=(rows, n, lanes)) for n in keys]
    keeps = []
    for n in keys:
        if block:
            keep = np.repeat(rng.random((rows, n_q, n // block)) < share,
                             block, axis=-1)
        else:
            keep = rng.random((rows, n_q, n)) < share
        keeps.append(keep)
    for part, lo, hi in dead:
        keeps[part][:, :, lo:hi] = False
    if blind is not None:
        keeps[blind[0]][0, blind[1]:] = False
    own = np.arange(keys[-1])[None, :] <= np.arange(n_q)[:, None] \
        * keys[-1] // n_q
    keeps[-1] = (keeps[-1] | np.eye(n_q, keys[-1], dtype=bool)[None]) & own
    keeps[-1][:, :, 0] = True
    q, ks, vs = ([jnp.asarray(x, jnp.float32) for x in xs]
                 for xs in ([q], ks, vs))
    return q[0], ks, vs, [jnp.asarray(keep) for keep in keeps]


CASES = {
    # a KV group of keye's (8 query heads) and of SALA's (16)
    "r8_token_mask_one_part": dict(rows=2, n_q=64, heads=8, keys=[1024]),
    "r16_block_mask_one_part": dict(rows=1, n_q=32, heads=16, keys=[1024],
                                    block=64),
    "r8_token_mask_two_parts": dict(rows=2, n_q=64, heads=8,
                                    keys=[1536, 128]),
    "r16_block_mask_two_parts": dict(rows=1, n_q=64, heads=16,
                                     keys=[1024, 256], block=64),
    # 40 queries: padded to 64, two tiles of 32; 200: padded to 224
    "queries_no_multiple_of_the_tile": dict(rows=1, n_q=40, heads=8,
                                            keys=[512, 128]),
    "several_query_tiles": dict(rows=1, n_q=200, heads=8, keys=[512]),
    # key blocks of 512 that hold no kept key are never read
    "dead_block_at_the_start": dict(rows=2, n_q=32, heads=8,
                                    keys=[2048, 128], dead=[(0, 0, 512)]),
    "dead_block_in_the_middle": dict(rows=2, n_q=32, heads=8,
                                     keys=[2048, 128],
                                     dead=[(0, 512, 1536)]),
    "dead_blocks_at_the_end": dict(rows=2, n_q=32, heads=16,
                                   keys=[2048, 128], block=64,
                                   dead=[(0, 1024, 2048)]),
    "a_whole_part_dead": dict(rows=1, n_q=32, heads=8, keys=[1024, 128],
                              dead=[(0, 0, 1024)]),
    # some rows keep nothing of the first part while others of their tile do
    "a_row_blind_to_a_part": dict(rows=2, n_q=32, heads=8, keys=[1024, 128],
                                  blind=(0, 7)),
    # scores of +-60: a later block's maximum rescales what came before,
    # within a part and from one part to the next
    "maxima_far_apart": dict(rows=1, n_q=32, heads=8, keys=[1024, 128],
                             scale=6.0, seed=3),
    # qwen3_next's gated layer: a KV group of 8 heads of 256 lanes, a tile of
    # 128 queries (1,024 rows, the cell's), the mask causal and nothing
    # else: a window live to a position that is no whole key block, a first
    # span's own rows alone, a window whose end is two dead blocks
    "heads_of_256_window_live_to_no_whole_block": dict(
        rows=1, n_q=128, heads=8, keys=[1024, 128], lanes=256, share=1.0,
        dead=[(0, 700, 1024)]),
    "heads_of_256_own_rows_only": dict(rows=2, n_q=128, heads=8, keys=[128],
                                       lanes=256, share=1.0),
    "heads_of_256_dead_end_of_whole_blocks": dict(
        rows=1, n_q=64, heads=8, keys=[2048, 128], lanes=256, share=1.0,
        dead=[(0, 1024, 2048)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_agrees_with_the_einsums(name, monkeypatch):
    q, ks, vs, keeps = _case(**CASES[name])
    want, took = decoder.attend_masked(q, ks, vs, keeps)
    assert took == 0
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    got, took = decoder.attend_masked(q, ks, vs, keeps)
    assert took == 1 and got.shape == want.shape
    assert np.isfinite(np.asarray(got)).all()
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= 1e-6 * float(jnp.max(want) - jnp.min(want))


def test_tables_skip_blocks_that_keep_nothing():
    """`run` is whether a (row, query tile, key block) keeps any key;
    `fetch` repeats the last running block's index (the first's, before
    it), so a skipped block is never copied."""
    keep = np.zeros((2, 64, 2048), bool)
    keep[0, :32, 512:1024] = True           # row 0, tile 0: block 1 alone
    keep[0, 40, 0] = keep[0, 63, 2047] = True   # tile 1: blocks 0 and 3
    own = np.ones((2, 64, 128), bool)
    run, fetch = masked_attention.block_tables(
        [jnp.asarray(keep), jnp.asarray(own)], 32, [512, 128])
    run, fetch = (np.asarray(x).reshape(2, 2, 5) for x in (run, fetch))
    np.testing.assert_array_equal(run[0], [[0, 1, 0, 0, 1], [1, 0, 0, 1, 1]])
    np.testing.assert_array_equal(fetch[0], [[1, 1, 1, 1, 0],
                                             [0, 0, 0, 3, 0]])
    # a row that keeps nothing of a part reads its first block, once
    np.testing.assert_array_equal(run[1], [[0, 0, 0, 0, 1]] * 2)
    np.testing.assert_array_equal(fetch[1], [[0, 0, 0, 0, 0]] * 2)


def test_blocks_follow_the_shapes():
    assert masked_attention.key_block(16384) == 512
    assert masked_attention.key_block(14336) == 512
    assert masked_attention.key_block(640) == 128
    assert masked_attention.key_block(768) == 384
    assert masked_attention.key_block(200) == 0
    assert masked_attention.query_tile(256) == (128, 256)
    assert masked_attention.query_tile(64) == (64, 64)
    assert masked_attention.query_tile(16) == (32, 32)
    assert masked_attention.query_tile(200) == (32, 224)


@pytest.mark.parametrize("why, case, head_dim", [
    ("one_query_a_row", dict(rows=2, n_q=1, heads=16, keys=[1024, 128]), 128),
    ("under_a_tile_of_rows", dict(rows=2, n_q=8, heads=8, keys=[1024]), 128),
    ("keys_no_whole_block", dict(rows=1, n_q=32, heads=8, keys=[200]), 128),
    ("heads_no_whole_lanes", dict(rows=1, n_q=32, heads=8, keys=[128]), 64),
])
def test_seam_keeps_the_einsums_where_no_tile_fills(why, case, head_dim,
                                                    monkeypatch):
    """A decode step's call (one query a row: 8 or 16 rows a group), a part
    that is no whole number of key blocks and a head narrower than the
    lanes stay on the einsums on a backend that runs Mosaic too."""
    q, ks, vs, keeps = _case(**case)
    q, ks, vs = ([x[..., :head_dim] for x in xs] if isinstance(xs, list)
                 else xs[..., :head_dim] for xs in (q, ks, vs))
    want, _ = decoder.attend_masked(q, ks, vs, keeps)
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    got, took = decoder.attend_masked(q, ks, vs, keeps)
    assert took == 0
    np.testing.assert_array_equal(got, want)


def test_keye_span_takes_the_kernel_a_chunk_and_counts_one_call(monkeypatch):
    """keye's span attention (heads of 128, a cached window and its own
    rows, the indexer's selection, two query chunks) through the seam: the
    kernel's context is the einsums', and the call counts once."""
    cfg = dataclasses.replace(
        registry.get_model_config("pipeedge/test-tiny-keye"), index_topk=96)
    rng = np.random.default_rng(5)
    rows, span, width = 2, 128, 256

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q = draw(rows, span, cfg.num_attention_heads, 128)
    iq, iw = draw(rows, span, cfg.index_heads, cfg.index_head_dim), \
        draw(rows, span, cfg.index_heads)
    q_pos = 200 + jnp.arange(span)
    at = jnp.arange(width)
    parts = [
        (tuple(draw(rows, width, 128) for _ in range(cfg.kv_heads)),
         tuple(draw(rows, width, 128) for _ in range(cfg.kv_heads)),
         draw(rows, width, cfg.index_head_dim), at, at < 200),
        (tuple(draw(rows, 128, 128) for _ in range(cfg.kv_heads)),
         tuple(draw(rows, 128, 128) for _ in range(cfg.kv_heads)),
         draw(rows, 128, cfg.index_head_dim), 200 + jnp.arange(128), None)]
    # two chunks of 64 queries: 64 x 2 query heads a KV group fill a tile
    monkeypatch.setattr(decoder, "SCORE_BYTES", 64 * rows * (
        cfg.num_attention_heads // cfg.kv_heads) * (width + 128) * 4)
    want, scored, kept, took = keye.sparse_attention(q, iq, iw, q_pos, parts,
                                                     cfg)
    assert int(took) == 0
    monkeypatch.setattr(decoder, "_fused_mode", lambda: "interpret")
    got, scored_k, kept_k, took = keye.sparse_attention(q, iq, iw, q_pos,
                                                        parts, cfg)
    assert int(took) == 1
    assert (int(scored_k), int(kept_k)) == (int(scored), int(kept))
    assert int(kept) == rows * span * 96
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= 1e-6 * float(jnp.max(want) - jnp.min(want))
