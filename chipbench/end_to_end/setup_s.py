"""Seconds from process start to the end of warm-up: imports, inputs,
weights, ``repro.compile`` and the warm-up calls."""


def read(record, trace=None):
    return record["setup_s"]
