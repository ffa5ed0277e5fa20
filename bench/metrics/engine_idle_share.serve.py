"""Share of the traced window in which the device is idle while the engine
holds the host, in %: device 0 runs no op, and the innermost of the
program's records covering that instant (`program_spans.idle_by_record`)
is engine host work: a ``serve/`` span other than ``serve/sample`` (the
wait for the device), or a ``jax/`` compile or trace. At most
``idle_share.serve``; the rest of the idle time is outside the engine (the
benchmark's loop, the generator, the profiler)."""
from bench import program_spans


def engine_work(name):
    return name is not None and name != "serve/sample" and (
        name.startswith("serve/") or name.startswith("jax/"))


def read(layer):
    split = program_spans.idle_by_record(layer)
    if split is None:
        return None
    w0, w1 = layer["trace"].window
    return 100.0 * sum(v for k, v in split.items() if engine_work(k)) / \
        (w1 - w0)
