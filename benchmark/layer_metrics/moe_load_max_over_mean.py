"""Expert layer: rows the busiest held expert saw over the mean of the held
experts, worst layer, of the window's last step: the program's
``moe.load_max_over_mean`` gauge (1 is an even load)."""


def read(ctx, record):
    value = record.get("shape", {}).get("moe_load_max_over_mean")
    return None if value is None else float(value)
