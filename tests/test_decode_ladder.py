"""Two ladders of attend widths: the batch job's and everybody else's.

Cut from `test_decode.py`, whose `_long_pipe` it uses, so that `--dist
loadfile` gives the fleet tests there and the ladders here to two workers.
"""
import numpy as np
import pytest

from pipeedge_tpu.parallel import decode
from test_decode import _long_pipe


def _todays_bucket(pos_next, max_len, floor):
    """`attend_bucket` as it was before it took `per_octave`."""
    b = max(1, floor)
    while b < pos_next:
        b *= 2
    return min(b, max_len)


@pytest.mark.parametrize("floor, per_octave, grain, max_len", [
    (64, 1, None, 1024), (4, 1, None, 64), (1, 1, None, 100),
    (100, 1, 7, 1000), (4096, 1, 512, 16384),
    (64, 4, None, 1024), (64, 4, None, 1000), (4, 4, None, 512),
    (4096, 4, 512, 16384), (1024, 4, 128, 4096), (8192, 4, 1024, 32768),
    (64, 2, None, 4096), (64, 4, 8, 300), (6, 4, 3, 200),
])
def test_attend_ladder_properties(floor, per_octave, grain, max_len):
    """Every ladder: a width holds the live rows, never passes `max_len`
    and never shrinks as the cache grows; where the floor is whole grains
    every width is (the cap is where it is) and no two are closer than a
    grain. One width an octave is the function as it was, value for value;
    more cut the overshoot: past the floor a window is less than its
    `per_octave`-th part and a grain over what it needs."""
    step = floor if grain is None else grain
    widths = [decode.attend_bucket(p, max_len, floor, per_octave, grain)
              for p in range(1, max_len + 1)]
    assert all(w >= p for p, w in enumerate(widths, 1))
    assert max(widths) == max_len
    assert widths == sorted(widths)
    ladder = sorted(set(widths))
    assert ladder[0] == min(floor, max_len)
    if floor % step == 0:
        assert all(w % step == 0 for w in ladder if w != max_len)
        assert all(b - a >= step for a, b in zip(ladder, ladder[1:-1]))
    if per_octave == 1:
        assert widths == [_todays_bucket(p, max_len, floor)
                          for p in range(1, max_len + 1)]
    else:
        coarse = [_todays_bucket(p, max_len, floor)
                  for p in range(1, max_len + 1)]
        assert all(w <= c for w, c in zip(widths, coarse))
        # every power of two of today's ladder is still a width
        assert set(coarse) <= set(ladder)
        for p, w in enumerate(widths, 1):
            if w > floor:
                assert w - p < w / per_octave + step
    with pytest.raises(ValueError, match="exceeds"):
        decode.attend_bucket(max_len + 1, max_len, floor, per_octave, grain)


@pytest.mark.parametrize(
    "cell, max_len, prompt, span, new, octave, spans, steps", [
        ("gpt2-m.offline-batch", 1024, 256, None, 256, 4, [],
         [(320, 64), (384, 64), (448, 64), (512, 63)]),
        ("keye-vl2.long-batch", 16384, 15872, 512, 512, 4,
         [(4096, 8), (5120, 2), (6144, 2), (7168, 2), (8192, 2), (10240, 4),
          (12288, 4), (14336, 4), (16384, 3)], [(16384, 511)]),
        ("kimi-k2.agent-batch", 4096, 3072, 128, 1024, 4,
         [(1024, 8), (1280, 2), (1536, 2), (1792, 2), (2048, 2), (2560, 4),
          (3072, 4)], [(3584, 512), (4096, 511)]),
        # one block in four reads a window: two widths an octave
        ("qwen3-next.longdoc-batch", 32768, 31744, 1024, 1024, 2,
         [(8192, 8), (12288, 4), (16384, 4), (24576, 8), (32768, 7)],
         [(32768, 1023)]),
    ])
def test_the_job_ladder_gives_the_cells_their_widths(cell, max_len, prompt,
                                                     span, new, octave, spans,
                                                     steps):
    """The benchmark's four offline cells: the widths `generate()` asks for
    (`_read_len` at the pipeline's `job_per_octave`), each with the calls
    that bind it, are the design (ISSUE 34). A span's least width stays
    eight spans; a step's is the floor of 64."""
    import collections
    import types
    pipe = types.SimpleNamespace(_bucketed=True, max_len=max_len,
                                 attend_floor=64, keeps_positions=True)

    def read_len(pos, n=1, per_octave=octave):
        return decode.DecodePipeline._read_len(pipe, pos, n, per_octave)

    starts = range(0, prompt, span) if span else ()
    got = collections.Counter(read_len(start, span) for start in starts)
    assert sorted(got.items()) == spans
    got = collections.Counter(read_len(prompt + step - 1)
                              for step in range(1, new))
    assert sorted(got.items()) == steps
    # every other caller's widths are the powers of two they were
    for pos in range(0, max_len - (span or 1), 97):
        for n in (1, span or 4):
            floor = max(64, 8 * n if n > 1 else 0)
            assert read_len(pos, n, 1) == _todays_bucket(pos + n, max_len,
                                                         floor)


@pytest.mark.parametrize("model, octave", [
    ("pipeedge/test-tiny-gpt2", 4), ("pipeedge/test-tiny-keye", 4),
    ("pipeedge/test-tiny-kimi", 4), ("pipeedge/test-tiny-qwen3-next", 2),
])
def test_a_pipeline_knows_the_ladder_its_job_is_worth(model, octave):
    """`generate()` asks for four widths an octave where the window is what
    most blocks read, and for two where fewer than half of the blocks keep
    a row a position (qwen3_next: one in four): what a program more buys
    there is a quarter of what it buys elsewhere, and costs the same load."""
    pipe = decode.build_decode_pipeline(model, None, max_len=64)
    assert pipe.job_per_octave == octave
    assert decode.job_per_octave(None, pipe.stages) == decode.JOB_PER_OCTAVE


def _spy_widths(pipe):
    """Record (span, read_len, pos) of every stage program `pipe`
    dispatches (pos None where the call is traced into a larger program)."""
    seen = []
    for st in pipe.stages:
        def spy(params, data, cache, pos, _fn=st["decode"], **kw):
            seen.append((data.shape[1], kw.get("read_len"),
                         pos if isinstance(pos, int) else None))
            return _fn(params, data, cache, pos, **kw)
        st["decode"] = spy
    return seen


def _as_before(seen, max_len, attend_floor=4):
    """Every window bound from the host is a power of two times its least
    width, as `_read_len` gave it before there were two ladders."""
    from_host = [entry for entry in seen if entry[2] is not None]
    return bool(from_host) and all(
        width == _todays_bucket(pos + span, max_len, max(
            attend_floor, 8 * span if span > 1 else 0))
        for span, width, pos in from_host)


def _ladder_case(name):
    """(make(attend_floor) -> pipeline, max_len, prompt_len, new_tokens) of
    one family at a tiny size with room for several octaves."""
    import dataclasses

    from pipeedge_tpu.models import registry
    if name.startswith("gpt2"):
        bits = 8 if name.endswith("int8") else 0
        return (lambda floor: _long_pipe(128, seed=5, attend_floor=floor,
                                         cache_bits=bits)), 128, 5, 70
    model = {"mistral": "pipeedge/test-tiny-mistral",
             "keye": "pipeedge/test-tiny-keye"}[name]
    # mistral: a sliding window of 4 under windows of 4 to 64; keye: a
    # prompt prefilled in spans of 8 over windows of 64 to 160
    max_len, prompt, new = (64, 5, 50) if name == "mistral" else (256, 152, 6)
    cfg = dataclasses.replace(registry.get_model_config(model),
                              max_position_embeddings=max_len)
    total = registry.get_model_layers(model)
    params = registry.module_shard_factory(model, None, 1, total, stage=0,
                                           unroll=False)[1]
    family = registry.get_model_entry(model).family.FAMILY
    return (lambda floor: decode.DecodePipeline(
        family, cfg, [(1, total)], [params], max_len=max_len,
        attend_floor=floor)), max_len, prompt, new


@pytest.mark.parametrize("name", ["gpt2-fp", "gpt2-int8", "mistral", "keye"])
def test_generate_is_token_identical_under_every_ladder(name, monkeypatch):
    """`generate()` under the job's ladder, under the powers of two and over
    the full window gives the same tokens while the run crosses every width
    the fine ladder adds: float32 and int8 caches, a sliding window, and a
    prompt prefilled in spans through `_prefill`."""
    make, max_len, prompt, new = _ladder_case(name)
    ids = np.random.default_rng(17).integers(0, 100, size=(2, prompt))
    fine = make(4)
    fine_widths = _spy_widths(fine)
    got = np.asarray(fine.generate(ids, new))

    monkeypatch.setattr(decode, "JOB_PER_OCTAVE", 1)
    coarse = make(4)
    coarse_widths = _spy_widths(coarse)
    np.testing.assert_array_equal(np.asarray(coarse.generate(ids, new)), got)
    full = make(max_len)
    full_widths = _spy_widths(full)
    np.testing.assert_array_equal(np.asarray(full.generate(ids, new)), got)

    def ladder(seen):
        return {width for _, width, _ in seen}

    assert ladder(full_widths) == {max_len}
    assert len(fine_widths) == len(coarse_widths) == len(full_widths)
    assert _as_before(coarse_widths, max_len)
    assert not _as_before(fine_widths, max_len)
    # the fine ladder keeps the powers of two it passes and adds widths
    # between them
    added = ladder(fine_widths) - ladder(coarse_widths)
    assert len(added) >= 3 and ladder(fine_widths) - added
    assert sum(w for _, w, _ in fine_widths) \
        < sum(w for _, w, _ in coarse_widths)


def test_a_prefix_suffix_span_takes_the_job_ladder(monkeypatch):
    """`generate(prefix=)` runs the suffix as one span at the prefix's
    offset: off the job's ladder in `generate`, off the powers of two in
    `extend` as every other caller gets it; same tokens as the whole
    prompt."""
    pipe = _long_pipe(128, seed=5, attend_floor=4)
    ids = np.random.default_rng(19).integers(0, 100, size=(1, 79))
    want = np.asarray(pipe.generate(ids, 6))[:, 70:]
    handle = pipe.precompute_prefix(ids[0, :70])
    seen = _spy_widths(pipe)
    got = np.asarray(pipe.generate(ids[:, 70:], 6, prefix=handle))
    np.testing.assert_array_equal(got, want)
    # a span of 9 at 70: least width 72, widths 18 apart up to 144
    assert seen[0] == (9, 90, 70)
    caches = [decode._repeat_batch(c, 1) for c in handle["caches"]]
    pipe.extend(ids[:, 70:], caches, 70)
    assert seen[-1] == (9, 128, 70)     # 144, capped at max_len


def test_the_batcher_and_the_speculative_decoder_bind_todays_widths():
    """The server's executor, the speculative decoder and beam search
    dispatch through `_decode_step` and `extend` as before: every window
    they bind is a power of two times its least width (a server has to have
    compiled its programs before it takes traffic), over a run in which
    `generate()` on the same pipeline binds widths between them; the tokens
    are the same."""
    from pipeedge_tpu.parallel.batcher import ContinuousBatcher
    from pipeedge_tpu.parallel.speculative import SpeculativeDecoder

    pipe = _long_pipe(128, seed=5, attend_floor=4)
    seen = _spy_widths(pipe)
    ids = np.random.default_rng(23).integers(0, 100, size=(1, 37))
    want = np.asarray(pipe.generate(ids, 30))
    assert not _as_before(seen, 128)

    del seen[:]
    batcher = ContinuousBatcher(pipe, chunk_tokens=8)
    batcher.submit("r", ids, new_tokens=30)
    np.testing.assert_array_equal(batcher.run()["r"], want)
    # the chunks bind today's widths; the steps of the rows that stand
    # together bind none (they walk the cache up to the furthest row)
    assert {span for span, _, _ in seen} == {8, 5}      # 37 = 4 x 8 + 5
    assert _as_before(seen, 128), seen
    # a request that steps alone (sampled) binds them for its steps too
    sampled = np.asarray(pipe.generate(ids, 30, temperature=0.7, seed=1))
    del seen[:]
    batcher.submit("s", ids, new_tokens=30, temperature=0.7, seed=1)
    np.testing.assert_array_equal(batcher.run()["s"], sampled)
    steps_alone = [entry for entry in seen if entry[0] == 1]
    assert len(steps_alone) == 29 and _as_before(steps_alone, 128), seen

    draft = _long_pipe(128, seed=6, attend_floor=4)
    drafted = _spy_widths(draft)
    asked, inner = [], draft._read_len

    def asking(pos, span=1, per_octave=1):
        asked.append(per_octave)
        return inner(pos, span, per_octave)

    draft._read_len = asking
    for sync in ("host", "device"):     # device: the draft's round is one
        del seen[:], drafted[:]         # program, its window asked for first
        spec = SpeculativeDecoder(pipe, draft, gamma=3, sync=sync)
        np.testing.assert_array_equal(np.asarray(spec.generate(ids, 30)),
                                      want)
        assert _as_before(seen, 128), (sync, seen)
        assert sync == "device" or _as_before(drafted, 128), drafted
    assert set(asked) == {1}

    del seen[:]
    pipe.generate_beam(ids, 8, beams=2)
    assert _as_before(seen, 128), seen


def test_attend_counters_add_up_over_a_small_job():
    """`pipeedge_attend_positions_total`: over one `generate()` with a
    prefix, kind=read gains rows x span x the window bound and kind=live
    rows x span x the call's position, the suffix span under phase=prefill
    and the steps under phase=decode."""
    pipe = _long_pipe(128, seed=5, attend_floor=4)
    rows, prefix_len, suffix, new = 3, 70, 9, 12
    ids = np.random.default_rng(29).integers(0, 100, size=(rows, suffix))
    handle = pipe.precompute_prefix(np.arange(prefix_len) % 100)

    def counts():
        return {(phase, kind): decode.M_ATTEND.value(phase=phase, kind=kind)
                for phase in ("prefill", "decode")
                for kind in ("read", "live")}

    before = counts()
    seen = _spy_widths(pipe)
    pipe.generate(ids, new, prefix=handle)
    gain = {key: value - before[key] for key, value in counts().items()}
    assert seen[0][0] == suffix and len(seen) == new
    steps = range(prefix_len + suffix, prefix_len + suffix + new - 1)
    assert gain == {
        ("prefill", "read"): rows * suffix * seen[0][1],
        ("prefill", "live"): rows * suffix * prefix_len,
        ("decode", "read"): rows * sum(width for _, width, _ in seen[1:]),
        ("decode", "live"): rows * sum(steps)}
    assert 0 < gain["decode", "live"] < gain["decode", "read"]
