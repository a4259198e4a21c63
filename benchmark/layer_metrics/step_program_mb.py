"""Token-sequence trainer: the compiled step's generated code in MB, from
the program's gauge ``step.generated_code_bytes`` (set where a traced run
compiles the step once more for its scope table)."""

from harness import setup_spans


def read(ctx, record):
    code_bytes = setup_spans.gauge("step.generated_code_bytes")
    return None if code_bytes is None else code_bytes / 1e6
