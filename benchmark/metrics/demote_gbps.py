"""KV memory + tiers: demotion rate, the ``bytes`` of the program's
``kv.stage_out`` spans (its real pages, k and v) over their host wall, in
GB/s. Prints the count, bytes, padded bytes and the wall of the
``kv.gather``/``kv.d2h`` children."""
import program_spans


def read(v):
    return program_spans.move_rate(v, "kv.stage_out", ("kv.gather", "kv.d2h"))
