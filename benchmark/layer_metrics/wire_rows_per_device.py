"""Exchange: feature rows one device receives per epoch, exact from the
partitioning's shapes (harness/shapes.py)."""

from harness import shapes


def read(ctx, record):
    s = record.get("shape", {})
    if "partitions" not in s:
        return None
    return float(shapes.epoch_wire_rows_per_device(
        s["partitions"], s["vp"], len(s["layers"]) - 1
    ))
