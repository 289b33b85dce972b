"""Published peaks of one chip, by ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {path}; "
                       f"have {sorted(table)}")
    return table[device_kind]
