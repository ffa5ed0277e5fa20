"""Median queue wait, in ms, of the requests the engine admitted in the
window: each request's ``serve/queued`` record, from its submission to the
start of its admission (the program's own span; ``program_spans``)."""
from bench import program_spans, traffic


def read(layer):
    recs = program_spans.window(layer) or ()
    waits = [(r.end_ns - r.start_ns) * 1e-6 for r in recs
             if r.name == "serve/queued"]
    if not waits:
        return None
    return traffic.percentile(waits, 50)
