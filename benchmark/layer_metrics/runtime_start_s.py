"""Funnel: the runtime's start by the program's ``startup`` spans:
``process_prelude`` (from the kernel's start of the process to the
program's import: the interpreter, JAX's import and, in this benchmark, the
device gate that brings the backend up) plus every ``backend_init``."""

from harness import program_spans, setup_spans


def read(ctx, record):
    return setup_spans.runtime_start_s(program_spans.span_records())
