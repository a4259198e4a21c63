"""Serving: the longest single garbage collection of the interpreter
inside the window, in milliseconds (``gc.callbacks``, the benchmark's own
clock); 0 when none ran. A collection stops the server's threads and the
load generator together, so it shows in ``serve_p99_ms`` and in
``gen_late_ms_p99`` at once."""


def read(ctx, record):
    pauses = record.get("gc_pauses")
    if pauses is None:
        return None
    return max((seconds for _, seconds, _ in pauses), default=0.0) * 1000.0
