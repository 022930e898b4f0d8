"""The one table of peaks, keyed by ``device_kind``.  A device that is
not in the table is an error, never a default."""

import json
import pathlib

_TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks_for(device_kind):
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_TABLE.name}: add "
            "a row with its source"
        )
    return table[device_kind]
