"""Kernels: the least time the chip could take for the grouped SwiGLU over
the rows the window routed to held experts (``needs/<config's
need>.experts_need``, from the program's ``moe.rows_routed``), as a share
of the device time the step's ``seq/moe/experts`` scope took in the
trace."""

from harness import scope_reduce, shapes, spec


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    need_of = getattr(spec.config_module(ctx.config, "need"), "experts_need", None)
    if not by_scope or need_of is None or not by_scope.get("seq/moe/experts"):
        return None
    if not record["shape"].get("routed_rows"):
        return None
    least = shapes.least_time(need_of(record["shape"]), ctx.peaks, ctx.chips)
    return 100.0 * least["seconds"] / (by_scope["seq/moe/experts"] / record["epochs"])
