"""Serving: CPU time the host kept from this machine inside the window,
in milliseconds summed over its CPUs (/proc/stat's steal column, in ticks
of 10 ms). With ``host_gc_pause_ms_max`` it tells the two candidate causes
of the process-wide stalls that move ``serve_p99_ms`` apart: a stall with
neither is something else."""


def read(ctx, record):
    steal = record.get("cpu_steal_s")
    return None if steal is None else steal * 1000.0
