"""Share of the engine's slots live in the window's decode steps, in %:
the sum of ``live`` over the sum of ``n_slots`` on the window's
``serve/dispatch`` records (the program's counters; ``program_spans``)."""
from bench import program_spans


def read(layer):
    recs = [r for r in program_spans.window(layer) or ()
            if r.name == "serve/dispatch"]
    slots = sum(r.attrs["n_slots"] for r in recs)
    if not slots:
        return None
    return 100.0 * sum(r.attrs["live"] for r in recs) / slots
