"""Kernels (``kernels/decode_attention``, latent mode): the latent
decode-attention kernel's share of its roofline over the traced decode
steps. Per call the least time is the larger of the bytes the algorithm
needs over the peak bandwidth and its FLOPs over the peak rate
(``counts_mla``, ``counts.roofline_seconds``); the share is their sum
over the kernel's device time. Nothing is returned when the trace's
kernel calls do not match the decode steps recorded."""
import counts
import counts_mla
import trace_reduce


def read(v):
    t0, t1 = v.t_trace
    calls = [lens for t, lens in v.capture.decode_calls if t0 <= t < t1]
    L = v.dims.layers
    sec, n = trace_reduce.kernel_time(v.trace, v.table,
                                      "mla_decode_attention")
    if not calls or n != L * len(calls):
        return None
    least, bounds = 0.0, set()
    for lens in calls:
        n_tab = 1 << max(0, max(-(-(x + 1) // v.page) for x in lens)
                         - 1).bit_length()
        f, b = counts_mla.mla_decode_attention(lens, v.dims, page=v.page,
                                               n_tab=n_tab)
        t, bound = counts.roofline_seconds(f, b, v.peaks)
        least += L * t
        bounds.add(bound)
    print(f"mla_decode_attn_roofline: {n} kernel calls, bound "
          f"{'/'.join(sorted(bounds))}, least {least:.6f} s, device "
          f"{sec:.6f} s", flush=True)
    return 100.0 * least / sec
