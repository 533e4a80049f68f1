"""The chip's published peaks, by `device_kind`, from `peaks.json`."""

import json
import os


def peaks_for(device_kind):
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            "benchmark/peaks.json; add them with their source"
        )
    return table[device_kind]
