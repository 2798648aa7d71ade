"""decode.make_next_picker: the one program between two stage programs
picks what the eager formulation picked, token for token and key for key."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipeedge_tpu.parallel import decode

VOCAB, PICKS = 97, 5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("span", [1, 7])
@pytest.mark.parametrize("temperature, top_k", [(0.0, 0), (0.8, 40)],
                         ids=["greedy", "t0.8-k40"])
def test_next_picker_equals_the_eager_formulation(temperature, top_k, span,
                                                  batch, dtype):
    """Last position, split, float32 cast, `make_token_picker`, re-entry
    shape: written out here as `generate` and the executors once made
    them, one dispatch each. PICKS picks in a row are PICKS splits."""
    outs = jax.random.normal(jax.random.PRNGKey(3),
                             (PICKS, batch, span, VOCAB)).astype(dtype)
    pick_next = decode.make_next_picker(temperature, top_k)
    pick = decode.make_token_picker(temperature, top_k)
    rng = want_rng = jax.random.PRNGKey(11)
    for out in outs:
        token, ids, rng = pick_next(out, rng)
        want_rng, sub = jax.random.split(want_rng)
        want = pick(out[:, -1].astype(jnp.float32), sub)
        np.testing.assert_array_equal(token, want)
        np.testing.assert_array_equal(ids, want[:, None])
        np.testing.assert_array_equal(rng, want_rng)
        assert (token.shape, ids.shape, ids.dtype) == (
            (batch,), (batch, 1), jnp.int32)
