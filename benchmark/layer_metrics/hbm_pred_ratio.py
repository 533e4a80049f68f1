"""The static memory verifier's predicted peak per device over XLA's own
bytes for the compiled step (1 = the verifier can be trusted as a gate)."""

LAYER = "static verifiers"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "step_hbm_gb"


def read(ctx):
    prov = ctx["provenance"]
    memory = prov.get("memory") if isinstance(prov, dict) else None
    # bytes by device; XLA's figure is per chip, so take the fullest
    predicted = (memory or {}).get("predicted_peak_bytes_full_mesh")
    if not predicted:
        return None
    return float(max(predicted.values())) / ctx["step_bytes"]["total"]
