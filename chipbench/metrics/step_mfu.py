"""Model FLOP utilisation of the whole step: the operations the model needs
for the tokens delivered in the traced window, over the window's length
times the chip's bf16 peak, in percent.

A request's first token costs its prompt's prefill (every position through
every layer, causal attention, the head at the last position); token i > 0
costs one decode at a context of prompt + i tokens. Padding, dead table
columns and logits nobody reads are not counted."""

from chipbench import peaks


def compute(run):
    tr = run.trace
    if tr is None or tr.window_ns <= 0:
        return None
    m = run.model
    flops = 0.0
    for r, i, _ in run.deliveries():
        plen = len(r.prompt)
        flops += (peaks.prefill_flops(m, plen) if i == 0
                  else peaks.decode_flops(m, plen + i))
    if flops == 0:
        return None
    return 100.0 * flops / (tr.window_ns / 1e9 * run.peaks["bf16_flops"])
