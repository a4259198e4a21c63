"""Device, under served traffic: ``device_idle_share`` under the name
whose ``moves`` is a serving metric (a per-layer metric names one
end-to-end metric, and is reported only in the cells that report it)."""

from harness import spec

read = spec.layer_reader("device_idle_share")
