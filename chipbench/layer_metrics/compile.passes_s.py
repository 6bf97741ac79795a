"""Seconds of the compile driver's passes (``CompiledNet.passes``)."""


def read(record, trace=None):
    return record["passes_s"]
