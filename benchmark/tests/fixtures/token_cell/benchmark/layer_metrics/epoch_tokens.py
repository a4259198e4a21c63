"""Trainers: token positions one epoch trains on, from the inputs' shape."""


def read(ctx, record):
    tokens = record.get("shape", {}).get("tokens")
    return None if tokens is None else float(tokens)
