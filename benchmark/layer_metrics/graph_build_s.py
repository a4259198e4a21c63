"""Funnel: seconds from the start of the host graph (generated and built,
or loaded from benchmark/.cache) to a built trainer: tables, uploads, and
for a served cell the checkpoint, the engine and its ladder. The
benchmark's own spans around those calls."""


def read(ctx, record):
    spans = ctx.spans
    return (spans["graph_s"] + spans["datum_s"] + spans["trainer_build_s"]
            + spans.get("server_build_s", 0.0))
