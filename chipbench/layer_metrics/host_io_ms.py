"""Milliseconds per traced call of the host I/O spans' own time: the
self time of ``vmcu.quantize``, ``vmcu.dequantize``, ``vmcu.stage`` and
``vmcu.fetch`` (``spans.py``; their ``vmcu.sync`` waits left out)."""

SPANS = ("vmcu.quantize", "vmcu.dequantize", "vmcu.stage", "vmcu.fetch")


def read(record, trace=None):
    if not trace or not trace.get("spans") or not record["traced"]["calls"]:
        return None
    own = sum(trace["spans"].get(n, {}).get("self_s", 0.0) for n in SPANS)
    return 1e3 * own / record["traced"]["calls"]
