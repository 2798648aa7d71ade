"""Fused Pallas quant codec: bit-identity + dispatch-seam tests.

The acceptance invariant of ops/fused_quant.py: for every supported
bitwidth and shape — including odd tails that exercise the nibble/byte
packing's zero-padded last word — the fused encode produces the SAME
packed words, scale, and shift as `ops/quant.py tensor_encode_outerdim`,
and the fused decode matches `tensor_decode_outerdim`. Tier-1 on CPU via
Pallas interpret mode (the kernels' math without TPU hardware); the
shared `_blocks.pick_block` resolver is covered here too since the fused
kernels and both attention kernel families now use the one definition.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.ops import fused_quant, quant
from pipeedge_tpu.ops._blocks import pick_block

# odd-tail matrix: n % per_word sweeps 0 (exact words) and nonzero tails
# for both the int8 (4/word) and int4 (8/word) packings
SHAPES = [
    (2, 37),        # int8: 1-value tail; int4: 5-value tail
    (2, 197, 128),  # int8: 6304 words, a ragged second decode lane block
    (3, 128),       # exact words both widths
    (1, 5),         # sub-word single item
    (4, 7, 9),      # multi-dim inner shape, 63 values: 3-tail / 7-tail
    (8, 197, 64),   # ViT-ish: per-item 12608 values, exact int8 words
    (5, 33),        # int8 1-tail, int4 1-tail
]


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    # mixed-sign, non-unit range so scale/shift are non-trivial
    return jnp.asarray((rng.normal(size=shape) * 3.7 - 1.2)
                       .astype(np.float32))


@pytest.mark.parametrize("bit", fused_quant.FUSED_BITS)
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_encode_bit_identical(bit, shape):
    x = _rand(shape)
    enc = fused_quant.fused_encode_outerdim(x, bit, interpret=True)
    ref = quant.tensor_encode_outerdim(x, bit)
    assert enc.bit == ref.bit and enc.shape == ref.shape
    assert np.array_equal(np.asarray(enc.data), np.asarray(ref.data))
    assert np.array_equal(np.asarray(enc.scale), np.asarray(ref.scale))
    assert np.array_equal(np.asarray(enc.shift), np.asarray(ref.shift))


@pytest.mark.parametrize("bit", fused_quant.FUSED_BITS)
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_decode_bit_identical(bit, shape):
    x = _rand(shape, seed=1)
    ref = quant.tensor_encode_outerdim(x, bit)
    dec = fused_quant.fused_decode_outerdim(ref, interpret=True)
    refd = quant.tensor_decode_outerdim(ref)
    assert np.array_equal(np.asarray(dec), np.asarray(refd))


@pytest.mark.parametrize("bit", fused_quant.FUSED_BITS)
def test_cross_generation_pairing(bit):
    """Fused producer with XLA consumer and vice versa: the wire contract
    (comm/wire.py) — any encoder generation pairs with any decoder."""
    x = _rand((3, 41), seed=2)
    fused_enc = fused_quant.fused_encode_outerdim(x, bit, interpret=True)
    xla_dec = np.asarray(quant.tensor_decode_outerdim(fused_enc))
    xla_enc = quant.tensor_encode_outerdim(x, bit)
    fused_dec = np.asarray(
        fused_quant.fused_decode_outerdim(xla_enc, interpret=True))
    assert np.array_equal(xla_dec, fused_dec)


def test_nibble_packing_layout(monkeypatch):
    """int4 values land at their reference bit offsets: value i sits in
    word i//8 at bit (i%8)*4 (reference basic_op.py layout)."""
    vals = np.arange(16, dtype=np.float32)  # identity under 4-bit encode
    x = jnp.asarray(vals[None])             # one item, exact 2 words
    enc = fused_quant.fused_encode_outerdim(x, 4, interpret=True)
    words = np.asarray(enc.data)[0]
    unpacked = [(int(words[i // 8]) >> ((i % 8) * 4)) & 0xF
                for i in range(16)]
    assert unpacked == list(range(16))


def test_zero_range_item():
    """Constant items (scale == 0) must not NaN — the quant.py guard."""
    x = jnp.ones((2, 19), jnp.float32) * 4.5
    for bit in fused_quant.FUSED_BITS:
        enc = fused_quant.fused_encode_outerdim(x, bit, interpret=True)
        ref = quant.tensor_encode_outerdim(x, bit)
        assert np.array_equal(np.asarray(enc.data), np.asarray(ref.data))
        dec = np.asarray(fused_quant.fused_decode_outerdim(enc,
                                                           interpret=True))
        assert np.allclose(dec, 4.5)


def test_unsupported_bit_raises():
    x = _rand((2, 8))
    with pytest.raises(ValueError):
        fused_quant.fused_encode_outerdim(x, 6, interpret=True)


# -- dispatch seam --------------------------------------------------------

def test_seam_interpret_mode(monkeypatch):
    """PIPEEDGE_FUSED_QUANT=interpret routes the seam through the Pallas
    kernels (CPU CI path) and stays bit-identical to the XLA ops."""
    monkeypatch.setenv(fused_quant.ENV_FUSED_QUANT, "interpret")
    x = _rand((4, 29), seed=3)
    for bit in fused_quant.FUSED_BITS:
        assert fused_quant.fused_available(bit)
        enc = fused_quant.encode_outerdim(x, bit)
        ref = quant.tensor_encode_outerdim(x, bit)
        assert np.array_equal(np.asarray(enc.data), np.asarray(ref.data))
        dec = fused_quant.decode_outerdim(enc)
        assert np.array_equal(np.asarray(dec),
                              np.asarray(quant.tensor_decode_outerdim(ref)))


def test_seam_off_and_auto_on_cpu(monkeypatch):
    """'0' forces the XLA ops; 'auto' on a CPU backend also stays XLA (no
    native Mosaic kernels off-TPU) — both must still round-trip."""
    x = _rand((2, 11), seed=4)
    for mode in ("0", "auto"):
        monkeypatch.setenv(fused_quant.ENV_FUSED_QUANT, mode)
        assert not fused_quant.fused_available(8)
        enc = fused_quant.encode_outerdim(x, 8)
        ref = quant.tensor_encode_outerdim(x, 8)
        assert np.array_equal(np.asarray(enc.data), np.asarray(ref.data))


def test_seam_unfused_bits_fall_back(monkeypatch):
    """Bitwidths without a fused kernel (e.g. 16) silently use the XLA
    ops even in forced-fused modes — the adaptive-bitwidth policies pick
    from the full SUPPORTED_BITS set."""
    monkeypatch.setenv(fused_quant.ENV_FUSED_QUANT, "interpret")
    x = _rand((2, 10), seed=5)
    enc = fused_quant.encode_outerdim(x, 16)
    ref = quant.tensor_encode_outerdim(x, 16)
    assert np.array_equal(np.asarray(enc.data), np.asarray(ref.data))
    assert np.array_equal(
        np.asarray(fused_quant.decode_outerdim(enc)),
        np.asarray(quant.tensor_decode_outerdim(ref)))


# -- shared block resolver (ops/_blocks.py) -------------------------------

def test_pick_block_divides_and_aligns():
    for width in (8, 24, 128, 136, 1024, 4096):
        b = pick_block(width, 128)
        assert width % b == 0
        assert b % 8 == 0 or b == width
        assert b <= max(128, width)


def test_pick_block_fallback_full_width():
    # prime width > preferred: no multiple of 8 divides it -> full width
    assert pick_block(97, 64) == 97
    # tiny widths fall through to the full extent
    assert pick_block(5, 128) == 5


def test_pick_block_is_the_shared_resolver():
    """The three pre-dedup copies (attention.py, decode_attention.py x2)
    now alias the one definition."""
    from pipeedge_tpu.ops import attention, decode_attention
    assert attention._pick_block is pick_block
    assert decode_attention._pick_block is pick_block
