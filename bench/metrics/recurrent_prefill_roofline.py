"""Share of its roofline that the recurrent prefill program reaches.

Programs: those the family's ``prefill_batch`` calls launched in the traced
window, chosen by name: ``jit_recurrent_lm_prefill`` (the token-serial scan
over the chunk and the head on each row's last fed position) and
``jit__take_rows`` (the gather of the requested rows).

Least time per call: the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, from ``fam.prefill_cost`` for the call's real
rows: the layers' weights read once per call (not once per scan step),
each row's state read and written once per layer, and the head for the
rows that complete their prompt.  The share is the least time of all
traced calls over the programs' device time.
"""
from bench.readers import prefill_rows, roofline_pct

PROGRAMS = ("jit_recurrent_lm_prefill(", "jit__take_rows(")


def read(run):
    cost_of = getattr(run.fam, "prefill_cost", None)
    if cost_of is None:
        return None
    cost = [cost_of(run.cfg, counts, completing)
            for _, counts, completing in prefill_rows(run)]
    time_ns = sum(m.end - m.start for m in run.trace.modules
                  if m.kind == "prefill_batch" and m.name.startswith(PROGRAMS))
    return roofline_pct(run, cost, time_ns)
