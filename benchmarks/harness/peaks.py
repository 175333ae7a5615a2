"""The chip's published peaks, keyed by `device_kind` (peaks.json)."""
import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind):
    with open(_FILE) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_FILE}: "
                       "add it with its source, there is no default")
    return table[device_kind]
