"""Milliseconds per traced step of the stream session's own host time:
the self time of ``vmcu.step``, the session's Python outside the
``vmcu.*`` spans of its layers (``spans.py``).  ``None`` where the trace
holds no ``vmcu.step``."""


def read(record, trace=None):
    step = ((trace or {}).get("spans") or {}).get("vmcu.step")
    if not step or not record["traced"]["calls"]:
        return None
    return 1e3 * step["self_s"] / record["traced"]["calls"]
