"""Share of its roofline that the per-layer decode program reaches: the
least time the chip could take for every decode call of a layer in the
window (the larger of its operations over peak bf16 FLOP/s and its bytes
over HBM bandwidth, each call) over the device time of the
``jit_decode_fn`` program in the trace.  Operations and bytes are the
algorithm's at the stated precision: the layer's weights and the live KV
rows of each row in the batch (``bench/costs.py``)."""
import costs

PROGRAM = "jit_decode_fn"


def read(run):
    if run.device is None:
        return None
    t = run.device.program_s().get(PROGRAM, 0.0)
    if not t:
        return None
    c, pk = run.cell.config, run.peaks
    least = 0.0
    for rows, ctx in decode_calls(run):
        f, b = costs.decode_unit(c, rows, ctx)
        least += c["num_layers"] * max(f / pk["bf16_flops"],
                                       b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t if least else None


def decode_calls(run):
    """(rows, positions attended in all) of each decode step in the
    window, from the tokens it emitted: token k >= 1 of a request attends
    its prompt and k positions."""
    out = []
    for s in run.steps:
        if not s.decode_steps:
            continue
        toks = [(r, i) for r in run.requests for i, t in enumerate(r.t_tokens)
                if i >= 1 and s.t0 <= t <= s.t1]
        if toks:
            out.append((len(toks), sum(len(r.prompt) + i for r, i in toks)))
    return out
