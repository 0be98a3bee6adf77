"""Share of its roofline that the paged decode attention kernel reaches.

Kernel: in the programs of ``decode_steps`` calls, the Pallas calls
(``custom_call_target="tpu_custom_call"``) that take three scalar-prefetch
``s32`` operands (page table, lengths, pages in use) and alias no operand:
one per layer and step.  (The K/V append converters alias the pool and take
one ``s32`` operand.)  The trace names no kernel today, so this signature
is how it is found.

Least time per call: the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, from ``fam.decode_kernel``: the K/V of every
active row's live tokens plus the rows' float32 q and out, for one layer.
The share is the least time of all traced calls over their device time.
"""
from bench.readers import decode_lens, roofline_pct
from bench.trace import is_pallas, scalar_operands


def is_kernel(text):
    return (is_pallas(text) and "output_to_operand_aliasing" not in text
            and scalar_operands(text) == 3)


def read(run):
    layers = run.cfg["num_hidden_layers"]
    cost = [run.fam.decode_kernel(run.cfg, lens)
            for lens in decode_lens(run)] * layers
    return roofline_pct(run, cost,
                        run.trace.op_ns("decode_steps", is_kernel))
