"""The one table of peaks, keyed by ``device_kind``. A device that is not in
the table is an error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def _table() -> dict:
    with open(_PATH) as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    table = _table()
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PATH}: add its published "
            "peaks with their source; no share of a peak is computed without")
    return table[device_kind]


def peaks_or_none(device_kind: str, toy: bool):
    """A CPU rehearsal has no peaks: its shares of a peak are left out. A
    measurement on a device that is not in the table is an error."""
    if toy and device_kind not in _table():
        return None
    return peaks(device_kind)
