"""Least time of the work completed in the traced window (``work.py``,
from layer shapes) over the device's busy time in that window."""


def read(record, trace=None):
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * record["traced"]["calls"] * record["least_call_s"] / trace["busy_s"]
