"""Kernels: the least time the chip could take for the causal attention's
pairs of one step, all layers (``needs/<config's need>.attention_need``
over the published peaks), as a share of the device time the step's
``seq/mla/attend`` scope took in the trace."""

from harness import scope_reduce, shapes, spec


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    need_of = getattr(spec.config_module(ctx.config, "need"), "attention_need", None)
    if not by_scope or need_of is None or not by_scope.get("seq/mla/attend"):
        return None
    shape = record["shape"]
    need = need_of(shape)
    layers = shape["moe_layers"] + 1
    least = shapes.least_time({k: v * layers for k, v in need.items()}, ctx.peaks, ctx.chips)
    return 100.0 * least["seconds"] / (by_scope["seq/mla/attend"] / record["epochs"])
