"""The table of peaks, keyed by ``device_kind``: ``peaks.json`` beside this
file, each row with its source. An unknown kind is an error."""

from . import spec


def of(device_kind):
    table = spec.load_json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"benchmarks: no peaks for device kind "
                         f"{device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]
