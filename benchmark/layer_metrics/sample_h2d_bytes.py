"""Sampling: bytes of sampled batches shipped host to device over the
window, the program's ``sample.h2d_bytes`` counter. The fused epoch scan
ships none."""


def read(ctx, record):
    return record.get("sample_h2d_bytes")
