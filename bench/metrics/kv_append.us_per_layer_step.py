"""Device microseconds of the K/V append converters per layer and step.

Kernels: in the programs of ``decode_steps`` calls, the Pallas calls that
alias their output to an operand (``output_to_operand_aliasing``): the
indirect-scatter converters of ``ops.paged_kv_append``, one for K and one
for V per layer and step.  Their device time over (decode steps x layers).
"""
from bench.readers import decode_steps
from bench.trace import is_pallas


def is_kernel(text):
    return is_pallas(text) and "output_to_operand_aliasing" in text


def read(run):
    n = decode_steps(run) * run.cfg["num_hidden_layers"]
    t = run.trace.op_ns("decode_steps", is_kernel)
    return t / 1e3 / n if n and t else None
