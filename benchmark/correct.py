"""The comparisons that decide `correct`, outside the timed window.

Both hold the program's bfloat16 results to a float32 reference, so both
state a tolerance and its reason. Neither compares sampled tokens: with
random weights the largest logit changes on rounding."""
import importlib

import numpy as np

# bfloat16 keeps 8 bits of mantissa (unit roundoff 2**-9). Through 24 blocks
# the program's logits drift from a float32 reference by about one percent
# of the largest logit (PR 21 saw 0.9% between two bfloat16 partitions of
# ViT-L on the chip). 2**-5 = 3.1% gives that a factor of three and would
# still fail an 8-bit computation, whose step is 2**-4 of the range or worse.
BF16_LOGIT_TOLERANCE = 2.0 ** -5


def reference_module(config):
    return importlib.import_module(f"benchmark.reference.{config['reference']}")


def logits_agree(config, weights, inputs, logits,
                 tolerance=BF16_LOGIT_TOLERANCE):
    """Classifier: the program's `logits` for `inputs` against the
    reference's. Returns (ok, facts)."""
    wanted = np.asarray(reference_module(config).forward(
        config, weights, np.asarray(inputs, np.float32)))
    got = np.asarray(logits, np.float32)
    scale = float(np.abs(wanted).max())
    worst = float(np.abs(wanted - got).max())
    facts = {"max_abs_logit": scale, "max_abs_logit_diff": worst,
             "tolerance": tolerance * scale,
             "top1_agreement": float(np.mean(wanted.argmax(-1)
                                             == got.argmax(-1)))}
    ok = bool(np.isfinite(got).all() and got.shape == wanted.shape
              and worst <= tolerance * scale)
    return ok, facts


def tokens_near_greedy(config, weights, sequences, prompt_lens,
                       tolerance=BF16_LOGIT_TOLERANCE, pad_to=None):
    """Decoder: teacher-force the reference on each returned sequence
    (prompt and generated tokens) and require every generated token's
    reference logit to be within `tolerance` x the row's logit range of
    the reference's largest logit at that position. A greedy decoder
    computing in bfloat16 may pick another token than float32 would only
    where the two logits are that close. Returns (ok, facts)."""
    forward = reference_module(config).forward
    worst, checked, bad = 0.0, 0, 0
    for ids, prompt_len in zip(sequences, prompt_lens):
        ids = np.asarray(ids, np.int64)
        length = len(ids)
        padded = ids
        if pad_to and length < pad_to:      # one compiled shape; causal, so
            padded = np.concatenate(        # the padding changes nothing
                [ids, np.zeros(pad_to - length, np.int64)])
        logits = np.asarray(forward(config, weights, padded[None]))[0]
        for position in range(prompt_len, length):
            row = logits[position - 1]      # predicts the token at `position`
            spread = float(row.max() - row.min())
            gap = float(row.max() - row[ids[position]]) / spread
            worst = max(worst, gap)
            checked += 1
            bad += gap > tolerance
    facts = {"tokens_checked": checked, "tokens_outside": int(bad),
             "worst_gap_share_of_range": worst, "tolerance": tolerance}
    return bool(checked > 0 and bad == 0), facts
