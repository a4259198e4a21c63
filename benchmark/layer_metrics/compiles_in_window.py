"""Trainers / serving: requests made of the compiler or its cache inside
the measured window. Must be 0: every shape is warmed in set-up."""


def read(ctx, record):
    return float(record["compiles_in_window"])
