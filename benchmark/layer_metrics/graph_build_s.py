"""Funnel: seconds from the start of the inputs (a host graph generated and
built, or loaded from benchmark/.cache; the seed's datum) to a built
trainer: tables, uploads, and for a served cell the checkpoint, the engine
and its ladder. The benchmark's own spans around those calls, whichever of
them the configuration's inputs have."""

SPANS = ("graph_s", "datum_s", "trainer_build_s", "server_build_s")


def read(ctx, record):
    found = [ctx.spans[name] for name in SPANS if name in ctx.spans]
    return sum(found) if found else None
