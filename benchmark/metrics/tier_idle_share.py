"""KV memory + tiers: seconds with no device op while a ``kv.restore``
or ``kv.stage_out`` span of the program is open, over the traced window.
Prints the window's device-idle seconds by innermost program span
(``none``: under no span)."""
import program_spans


def read(v):
    return program_spans.tier_idle_share(v)
