"""Device milliseconds per decode step.

The device time of the programs that the family's ``decode_steps`` calls
launched in the traced window (trace line ``XLA Modules``, each execution
tied to its call through its ``run_id``), over the steps those calls ran
(their ``n``).  A step advances every active row by one token.
"""
from bench.readers import decode_steps, program_ms


def read(run):
    n = decode_steps(run)
    return program_ms(run, "decode_steps") / n if n else None
