"""Host time of a decode step, in ms: the median, over the window's
``serve/step`` records that admitted nothing (no ``serve/admit`` inside),
of the step's ``serve/prepare`` + ``serve/dispatch`` + ``serve/retire``
time, the host work a step does while the device waits for it (the
program's own spans; ``program_spans``)."""
import collections

from bench import program_spans, traffic

HOST = ("serve/prepare", "serve/dispatch", "serve/retire")


def read(layer):
    steps = [r for r in program_spans.window(layer) or ()
             if r.name == "serve/step"]
    if not steps:
        return None
    kids = collections.defaultdict(list)
    for r in program_spans.ring():
        kids[r.parent].append(r)
    host = []
    for st in steps:
        names = [k.name for k in kids[st.id]]
        if "serve/admit" in names or "serve/dispatch" not in names:
            continue
        host.append(sum(k.end_ns - k.start_ns for k in kids[st.id]
                        if k.name in HOST) * 1e-6)
    if not host:
        return None
    return traffic.percentile(host, 50)
