"""Tokens the server streamed in the window over the stage-steps its
executor dispatched at stage 0 (`stage`/`exec0` spans: one per prefill and
one per decode step). A count: 1.0 while every dispatch advances one
request's one row."""


def read(observed):
    spans = observed.get("spans")
    if not spans or observed.get("spans_dropped"):
        return None
    steps = sum(1 for span in spans
                if span["cat"] == "stage" and span["name"] == "exec0")
    tokens = observed["summary"]["streamed_tokens"]
    return tokens / steps if steps else None
