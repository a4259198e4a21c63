"""Token-sequence trainer: share (%) of the device's busy time in the
traced window under the delta-rule mixer's scopes, ``seq/kda/*``
(projections, convolutions, gate, recurrence, gated norm): how much of the
step the linear-attention layers are."""

from harness import scope_reduce

PREFIX = "seq/kda/"


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    if not by_scope or not any(s.startswith(PREFIX) for s in by_scope):
        return None
    busy = sum(by_scope.values())
    mixer = sum(v for s, v in by_scope.items() if s.startswith(PREFIX))
    return 100.0 * mixer / busy if busy > 0 else None
