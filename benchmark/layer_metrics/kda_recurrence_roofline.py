"""Kernels: the least time the chip could take for the delta rule's
recurrence of one step, all KDA layers (``needs/<config's
need>.recurrence_need`` over the published peaks), as a share of the device
time the step's ``seq/kda/recur`` scope took in the trace."""

from harness import scope_reduce, shapes, spec

SCOPE = "seq/kda/recur"


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    need_of = getattr(spec.config_module(ctx.config, "need"), "recurrence_need", None)
    if not by_scope or need_of is None or not by_scope.get(SCOPE):
        return None
    if not record["shape"].get("kda_token_layers"):
        return None
    least = shapes.least_time(need_of(record["shape"]), ctx.peaks, ctx.chips)
    return 100.0 * least["seconds"] / (by_scope[SCOPE] / record["epochs"])
