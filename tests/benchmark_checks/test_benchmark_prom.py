"""Reading `/metrics`: samples, a histogram's gain between two scrapes, and
a quantile inside its bucket."""
import math

import pytest

from benchmark import prom

BEFORE = """# HELP pipeedge_admission_latency_seconds time to slot
# TYPE pipeedge_admission_latency_seconds histogram
pipeedge_admission_latency_seconds_bucket{class="interactive",le="0.001"} 2
pipeedge_admission_latency_seconds_bucket{class="interactive",le="0.01"} 2
pipeedge_admission_latency_seconds_bucket{class="interactive",le="+Inf"} 2
pipeedge_admission_latency_seconds_count{class="interactive"} 2
pipeedge_serve_tokens_total 10
"""
AFTER = BEFORE.replace('le="0.001"} 2', 'le="0.001"} 12') \
    .replace('le="0.01"} 2', 'le="0.01"} 20') \
    .replace('le="+Inf"} 2', 'le="+Inf"} 22') \
    .replace("tokens_total 10", "tokens_total 510")


def test_samples_carry_their_labels_and_values():
    rows = prom.samples(AFTER, "pipeedge_admission_latency_seconds_bucket")
    assert rows[0] == ({"class": "interactive", "le": "0.001"}, 12.0)
    assert prom.samples(AFTER, "pipeedge_serve_tokens_total") == [({}, 510.0)]


def test_a_histograms_gain_leaves_out_what_was_there_before():
    buckets = prom.histogram_delta(
        BEFORE, AFTER, "pipeedge_admission_latency_seconds")
    assert buckets == [(0.001, 10.0), (0.01, 8.0), (math.inf, 2.0)]


@pytest.mark.parametrize("q,expected", [
    (0.25, 0.0005),                 # the 5th of 10 in the first bucket
    (0.5, 0.001),                   # the 10th of 20: the first bound
    (0.9, 0.01),                    # the last of the middle bucket
    (0.95, 0.01),                   # falls in +Inf: the last finite bound
])
def test_a_quantile_is_interpolated_inside_its_bucket(q, expected):
    buckets = [(0.001, 10.0), (0.01, 8.0), (math.inf, 2.0)]
    assert prom.histogram_quantile(buckets, q) == pytest.approx(expected)


def test_an_empty_histogram_has_no_quantile():
    assert prom.histogram_quantile([(0.1, 0.0), (math.inf, 0.0)], 0.95) is None
