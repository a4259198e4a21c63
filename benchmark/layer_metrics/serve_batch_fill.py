"""Serving: seeds computed over bucket rows executed, from the program's
``serve.computed_seeds`` / ``serve.padded_seeds`` counters over the
window. 100 means no padding."""


def read(ctx, record):
    c = record.get("counters")
    if not c:
        return None
    rows = c["serve.computed_seeds"] + c["serve.padded_seeds"]
    return 100.0 * c["serve.computed_seeds"] / rows if rows else None
