"""The table of peaks (``peaks.json``, with its source), keyed by the
device kind JAX reports.  A device that is not in the table is an error,
not a default."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to cellbench/peaks.json with its source")
    return table[device_kind]
