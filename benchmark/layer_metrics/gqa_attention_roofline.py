"""Kernels: the least time the chip could take for the grouped-query causal
attention's pairs of one step (``needs/<config's need>.attention_need``,
one layer: the causal half, K and V read once a group, over the published
peaks, times ``attending_layers``: the layers that attend, read from the
cell's shape and not assumed from its depth), as a share of the device
time the step's ``seq/gqa/attend`` scope took in the trace."""

from harness import scope_reduce, shapes, spec

SCOPE = "seq/gqa/attend"


def read(ctx, record):
    by_scope = scope_reduce.of_run(ctx, record)
    need = spec.config_module(ctx.config, "need")
    need_of, layers_of = getattr(need, "attention_need", None), getattr(need, "attending_layers", None)
    if not by_scope or need_of is None or layers_of is None or not by_scope.get(SCOPE):
        return None
    if not record["shape"].get("gqa_token_layers"):
        return None
    layers = layers_of(record["shape"])
    least = shapes.least_time({k: v * layers for k, v in need_of(record["shape"]).items()},
                              ctx.peaks, ctx.chips)
    return 100.0 * least["seconds"] / (by_scope[SCOPE] / record["epochs"])
