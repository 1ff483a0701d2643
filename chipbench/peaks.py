"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``)."""

import json
import pathlib

TABLE_PATH = pathlib.Path(__file__).with_name("peaks.json")


def peak(device_kind: str, what: str) -> float:
    """A device that is not in the table is an error, not a default."""
    table = json.loads(TABLE_PATH.read_text())["devices"]
    try:
        return table[device_kind][what]
    except KeyError:
        raise KeyError(f"no {what!r} peak for device kind {device_kind!r} in "
                       f"{TABLE_PATH.name}") from None
