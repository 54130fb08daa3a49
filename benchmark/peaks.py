"""Peaks of the chips this benchmark has run on: one file a chip under
``peaks/``, found by the ``device_kind`` JAX reports.  A device that has no
file is an error, never a default."""

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_of(device_kind: str) -> dict:
    for path in sorted(glob.glob(os.path.join(HERE, "peaks", "*.json"))):
        with open(path) as f:
            row = json.load(f)
        if row["device_kind"] == device_kind:
            return row
    raise KeyError(
        f"no peaks for device kind {device_kind!r}: add benchmark/peaks/<chip>.json with its source"
    )
