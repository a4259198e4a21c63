"""Exchange: feature rows one device receives per epoch, exact from the
partitioning's shapes (the configuration's ``need`` module, where it has
an exchange)."""

from harness import spec


def read(ctx, record):
    rows_of = getattr(spec.config_module(ctx.config, "need"), "wire_rows_per_device", None)
    if rows_of is None or "shape" not in record:
        return None
    rows = rows_of(record["shape"])
    return None if rows is None else float(rows)
