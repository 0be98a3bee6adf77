"""Device milliseconds per 1000 prompt tokens prefilled.

The device time of the programs that the family's ``prefill_batch`` calls
launched in the traced window, over the real (non-padding) prompt tokens
those calls carried.
"""
from bench.readers import prefill_rows, program_ms


def read(run):
    tokens = sum(int(c.sum()) for _, c, _ in prefill_rows(run))
    return program_ms(run, "prefill_batch") / (tokens / 1e3) if tokens else None
