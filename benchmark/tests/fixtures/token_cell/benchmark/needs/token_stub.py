"""What one epoch of the fixture's bigram model needs, from shapes alone:
T token positions, width d, vocabulary n. Three products of 2 T d n
operations (forward, weight gradient, hidden gradient); each reads or
writes the [T, d] hidden rows and the [T, n] logits once. It has no
exchange, so it has no ``wire_rows_per_device``."""

from __future__ import annotations


def epoch_need(shape: dict) -> dict:
    t, d, n, size = shape["tokens"], shape["width"], shape["vocab"], shape["itemsize"]
    return {"flops": 3 * 2.0 * t * d * n, "bytes": 3.0 * t * (d + n) * size}
