"""Device: the whole latent/expert decode step's share of the chip's
bf16 peak: model FLOPs of the traced steps (``counts_mla``, the held
experts' part from the copies the program counted in its ``model.sync``
spans) over their device time times the peak. Nothing is returned when
the trace's step programs do not match the decode steps recorded, or
the program counted no copies."""
import counts_mla
import program_spans
import trace_reduce


def read(v):
    t0, t1 = v.t_trace
    calls = [lens for t, lens in v.capture.decode_calls if t0 <= t < t1]
    sec, n = trace_reduce.program_time(v.trace, v.table, "decode_step")
    syncs = [a for name, *_, a in program_spans.spans(v) or ()
             if name == "model.sync" and "held_copies" in a]
    if not calls or n != len(calls) or len(syncs) != len(calls):
        return None
    flops = sum(counts_mla.decode_step_flops(v.dims, lens, a["held_copies"])
                for lens, a in zip(calls, syncs))
    return 100.0 * flops / (sec * v.peaks["bf16_flops"])
