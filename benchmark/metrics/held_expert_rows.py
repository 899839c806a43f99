"""Experts: the mean token-copies each held expert computes per MoE
layer per decode step (the batch each expert's matmuls see), from the
``held_copies``, ``copies`` and ``moe_layers`` arguments of the
program's ``model.sync`` spans: their held copies over moe_layers x the
experts held, averaged over the traced decode steps. Prints the share of
all copies that were routed here."""
import program_spans


def read(v):
    syncs = [a for name, *_, a in program_spans.spans(v) or ()
             if name == "model.sync" and "held_copies" in a]
    if not syncs:
        return None
    held = sum(a["held_copies"] for a in syncs)
    slots = sum(a["moe_layers"] for a in syncs) * v.dims.held
    total = sum(a["copies"] for a in syncs)
    print(f"held_expert_rows: {len(syncs)} decode steps, {held} of {total} "
          f"copies routed to the {v.dims.held} held experts "
          f"({100.0 * held / total:.2f}%)", flush=True)
    return held / slots
