"""Share of the traced window in which the device is idle under a host
I/O span (``span_idle`` of ``vmcu.quantize``, ``vmcu.dequantize``,
``vmcu.stage`` and ``vmcu.fetch``, ``spans.py``)."""

SPANS = ("vmcu.quantize", "vmcu.dequantize", "vmcu.stage", "vmcu.fetch")


def read(record, trace=None):
    if not trace or not trace.get("spans") or trace["window_s"] <= 0:
        return None
    idle = sum(trace["span_idle"].get(n, 0.0) for n in SPANS)
    return 100.0 * idle / trace["window_s"]
