"""Fused decode chunk (the ``decode_chunk`` program, ``Model.decode_loop``):
its device time in the traced window per decode step it ran (each
execution runs ``chunk`` steps)."""
from bench.metrics_lib import program_ms, program_runs


def read(rec):
    runs = program_runs(rec, "decode_chunk")
    if not runs:
        return None
    return program_ms(rec, "decode_chunk") / (runs * rec["chunk"])
