"""The model's operations in the traced window over what the chip could
do in it: every token emitted at its context (an admission's first token
at the cost of its whole prompt) from the configuration's shapes
(``bench/costs.py``), over window seconds x chips x peak bf16 FLOP/s."""
import costs


def read(run):
    c = run.cell.config
    flops = 0
    for r, i in run.tokens_in_window():
        if i == 0:
            flops += sum(costs.token_flops(c, p + 1) for p in range(len(r.prompt)))
        else:
            flops += costs.token_flops(c, len(r.prompt) + i)
    if run.peaks is None or not flops:
        return None
    return 100.0 * flops / (run.window_s * run.chips * run.peaks["bf16_flops"])
