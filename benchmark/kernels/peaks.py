"""Peaks by ``device_kind``, from ``benchmark/peaks.json``.  A device that
is not in the table is an error, not a default."""
import json
import os


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "peaks.json")
    with open(path) as fp:
        table = json.load(fp)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}: add a row "
            "with its source, never a default"
        )
    return table[device_kind]
