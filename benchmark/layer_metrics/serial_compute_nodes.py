"""Compute nodes the searched winner leaves at degree 1 on a machine with
more than one device: each runs whole on every chip. A program whose
provenance has no such field reports nothing."""

LAYER = "search"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    prov = ctx["provenance"]
    if not isinstance(prov, dict) or "serial_compute_nodes" not in prov:
        return None
    return len(prov["serial_compute_nodes"])
