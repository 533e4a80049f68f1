"""Seconds the Unity search took, as its provenance says."""

LAYER = "search"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    prov = ctx["provenance"]
    if not isinstance(prov, dict) or "search_seconds" not in prov:
        return None
    return float(prov["search_seconds"])
