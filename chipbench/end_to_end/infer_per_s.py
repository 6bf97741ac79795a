"""Items completed over the whole measured window, per second."""


def read(record, trace=None):
    return record["items"] / record["elapsed_s"]
