"""KV memory + tiers: host-tier restore rate, the ``bytes`` of the
program's ``kv.restore`` spans (its real pages, k and v) over their host
wall, in GB/s. Prints the count, bytes, padded bytes, the wall of the
``kv.restore_pad``/``kv.h2d``/``kv.scatter`` children and the reload
seconds the scheduler priced (``priced_s``) beside the wall."""
import program_spans


def read(v):
    return program_spans.move_rate(
        v, "kv.restore", ("kv.restore_pad", "kv.h2d", "kv.scatter"),
        priced="priced_s")
