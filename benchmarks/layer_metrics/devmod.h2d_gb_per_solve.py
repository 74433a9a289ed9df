"""Bytes the device module staged host to device, per solve."""


def read(run: dict) -> float | None:
    win = run["window"]
    if not win.solves or not win.counters["bytes_in"]:
        return None
    return win.counters["bytes_in"] / win.solves / 1e9
