"""Share of its roofline that the per-layer decode program reaches: the
least time the chip could take for every decode call of every layer in
the window (the larger of its operations over peak bf16 FLOP/s and its
bytes over HBM bandwidth, each call of each layer, counted by the
layer's kind) over the device time of the ``jit_decode_fn`` program in
the trace.  Operations and bytes are the algorithm's at the stated
precision: the layer's weights and the live KV rows of each row in the
batch (``bench/costs.py``)."""
import costs

PROGRAM = "jit_decode_fn"


def read(run):
    if run.device is None:
        return None
    t = run.device.program_s().get(PROGRAM, 0.0)
    if not t:
        return None
    c, pk = run.cell.config, run.peaks
    kinds = costs.layer_counts(c)
    least = 0.0
    for ctxs in decode_calls(run):
        for layer, n in kinds.items():
            f, b = costs.decode_unit(c, ctxs, layer)
            least += n * max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t if least else None


def decode_calls(run):
    """The positions each live row attends, for each decode step in the
    window, from the tokens it emitted: token k >= 1 of a request attends
    its prompt and k positions."""
    out = []
    for s in run.steps:
        if not s.decode_steps:
            continue
        ctxs = [len(r.prompt) + i for r in run.requests
                for i, t in enumerate(r.t_tokens) if i >= 1 and s.t0 <= t <= s.t1]
        if ctxs:
            out.append(ctxs)
    return out
