"""The chip's peaks, from ``peaks.json`` (each with its source or
derivation).  A device kind that is not in the table is an error."""
from __future__ import annotations

import pathlib

from .spec import BENCH, read_json


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: pathlib.Path = BENCH / "peaks.json"):
    """{peak name: value} for ``device_kind``; raises `UnknownDevice`."""
    table = read_json(path)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return {k: float(v["value"]) for k, v in table[device_kind].items()}
