"""Expert layer: share (%) of the device's busy time in the traced window
under the scopes that move tokens and do no expert arithmetic:
``seq/moe/route`` (router product, scores, top-k), ``seq/moe/dispatch``
(sort by expert, gather) and ``seq/moe/combine`` (weighted gather back)."""

from harness import scope_reduce

MOVING = ("seq/moe/route", "seq/moe/dispatch", "seq/moe/combine")


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    if not by_scope or not any(s in by_scope for s in MOVING):
        return None
    busy = sum(by_scope.values())
    return 100.0 * sum(by_scope.get(s, 0.0) for s in MOVING) / busy if busy > 0 else None
