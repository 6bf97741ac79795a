"""95th percentile of every timed call in the window, in milliseconds."""
import numpy as np


def read(record, trace=None):
    return float(np.percentile(record["durations_s"], 95)) * 1e3
