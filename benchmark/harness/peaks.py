"""The table of published peaks, keyed by the `device_kind` JAX reports.

A device that is not in the table is an error, never a default: a roofline
share against the wrong chip's peaks is a wrong number under a right name.
"""

from __future__ import annotations

from harness.spec import BENCH_DIR, load_json


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "harness" / "peaks.json")
    try:
        return table[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(table)}"
        ) from None
