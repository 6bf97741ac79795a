"""Operations of the items completed in the traced window (``2 x`` MACs
from layer shapes) per second of that window, over the int8 peak."""


def read(record, trace=None):
    if not trace or trace["window_s"] <= 0:
        return None
    ops = 2 * record["macs_per_item"] * record["traced"]["items"]
    return 100.0 * ops / trace["window_s"] / record["peak_ops_per_s"]
