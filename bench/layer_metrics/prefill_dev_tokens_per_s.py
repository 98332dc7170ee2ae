"""Prompt tokens prefilled in the traced window over the device time of the
prefill programs there. The tokens are those of the requests whose first
token reached the client inside the traced window (a prefill ends a few
milliseconds before it), so a request astride an edge of the window is
counted whole or not at all."""


def read(facts, spec):
    trace = facts["trace"] or {}
    runs = trace.get("programs", {}).get(facts["metric"])
    if not runs:
        return None
    lo, hi = trace["t_start"], trace["t_stop"]
    tokens = sum(
        len(o.vocab.chat_ids(o.request.prompt_ids)) for o in facts["outcomes"]
        if o.arrivals and lo <= o.arrivals[0] < hi
    )
    return tokens / sum(runs) if tokens else None
