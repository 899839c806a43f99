"""Device: the whole decode step's share of the chip's bf16 peak: model
FLOPs of each traced step (``counts.decode_step_flops``) over its
device time times the peak. Nothing is returned when the trace's step
programs do not match the decode steps recorded."""
import counts
import trace_reduce


def read(v):
    t0, t1 = v.t_trace
    calls = [lens for t, lens in v.capture.decode_calls if t0 <= t < t1]
    sec, n = trace_reduce.program_time(v.trace, v.table, "decode_step")
    if not calls or n != len(calls):
        return None
    flops = sum(counts.decode_step_flops(v.dims, lens) for lens in calls)
    return 100.0 * flops / (sec * v.peaks["bf16_flops"])
