"""Model FLOP utilization of the whole traced window, in %, bf16 peak.

Model FLOPs of every prefill and decode token served in the traced window
(``fam.token_flops``; logits for each decode token and each completed
prompt) over the window's length times the bf16 peak: the share of the
chip the served work could have used, idle time and all.
"""
from bench.readers import decode_flops, prefill_flops


def read(run):
    if run.peaks is None or run.trace.window_s <= 0:
        return None
    flops = decode_flops(run) + prefill_flops(run)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_s"])
