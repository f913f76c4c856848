"""Tokens each held expert serves a layer and a step: the step log's
``moe_held_picks`` (each decoding row's (token, pick) pairs routed to an
expert this chip holds, summed over the expert layers) over the window's
steps that decoded, the expert layers and the experts held.  A program
without the counter, or a model without expert layers, gives None."""

from bench.steplog import window_records


def read(run):
    recs = window_records(run)
    layers = getattr(run.arch, "n_moe_layers", 0)
    held = getattr(run.arch, "n_held", 0)
    if recs is None or not layers or not held:
        return None
    decoded = [r for r in recs if r["decoded"]]
    if not decoded or "moe_held_picks" not in decoded[0]:
        return None
    picks = sum(r["moe_held_picks"] for r in decoded)
    return picks / (len(decoded) * layers * held)
