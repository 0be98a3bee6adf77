"""Share of its roofline that the recurrent decode program reaches.

Programs: ``jit_recurrent_lm_decode`` executions that the family's
``decode_steps`` calls launched in the traced window (``XLA Modules``, tied
to their calls by ``run_id``, and chosen by name so that no other program
the reduction ties to a call counts): the token-step scan of
``RecurrentLM``, every layer, the head and the argmax.

Least time per step: the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, from ``fam.decode_cost`` for the call's
active rows: the layers' and the head's weights read once, each active
row's state read and written in every layer.  The share is the least time
of all traced steps over the programs' device time.
"""
from bench.readers import roofline_pct, traced_calls

PROGRAM = "jit_recurrent_lm_decode("


def read(run):
    cost_of = getattr(run.fam, "decode_cost", None)
    if cost_of is None:
        return None
    cost = []
    for i in traced_calls(run, "decode_steps"):
        c = run.calls[i]
        cost += [cost_of(run.cfg, int(c["lens"].size))] * c["n"]
    time_ns = sum(m.end - m.start for m in run.trace.modules
                  if m.kind == "decode_steps" and m.name.startswith(PROGRAM))
    return roofline_pct(run, cost, time_ns)
