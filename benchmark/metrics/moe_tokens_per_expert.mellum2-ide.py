"""Assignments a touched expert is given in one expert layer's call of a
decode step, in the measured window of a served cell: decode-phase
`pipeedge_moe_assignments_total` over `pipeedge_moe_experts_touched_total`,
each differenced between the scrapes before and after the window (the server
is a child of the benchmark: its registry is read over `/metrics`). With 30
live rows stepping together and 8 of 64 experts a token it is about 3.75
(every expert touched): each expert's matrices are read once for nearly four
tokens. At one dispatch a request it is 1.0: eight experts, one token each,
and their matrices read once A REQUEST a step. Dead slots' rows go to no
expert and are not counted."""
from benchmark import prom


def _decode_gain(observed, name):
    def decode(text):
        return sum(value for labels, value in prom.samples(text, name)
                   if labels.get("phase") == "decode")
    if not prom.samples(observed.get("metrics_after", ""), name):
        return None
    return decode(observed["metrics_after"]) \
        - decode(observed.get("metrics_before", ""))


def read(observed):
    assignments = _decode_gain(observed, "pipeedge_moe_assignments_total")
    touched = _decode_gain(observed, "pipeedge_moe_experts_touched_total")
    if not assignments or not touched:
        return None
    return assignments / touched
