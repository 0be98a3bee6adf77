"""Share of its roofline that the paged prefill attention kernel reaches.

Kernel: in the programs of ``prefill_batch`` calls, the Pallas calls that
take four scalar-prefetch ``s32`` operands (context table, starts, counts,
pages in use) and alias no operand: one per layer and call.  (The chunk
write's gather and scatter converters take one ``s32`` operand.)

Least time per call: the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, from ``fam.prefill_kernel``: each row's
context K/V up to ``start + count``, plus the chunk's float32 q and out,
for one layer.
"""
from bench.readers import prefill_rows, roofline_pct
from bench.trace import is_pallas, scalar_operands


def is_kernel(text):
    return (is_pallas(text) and "output_to_operand_aliasing" not in text
            and scalar_operands(text) == 4)


def read(run):
    layers = run.cfg["num_hidden_layers"]
    cost = [run.fam.prefill_kernel(run.cfg, s, c)
            for s, c, _ in prefill_rows(run)] * layers
    return roofline_pct(run, cost,
                        run.trace.op_ns("prefill_batch", is_kernel))
