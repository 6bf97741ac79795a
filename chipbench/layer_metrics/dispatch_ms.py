"""Milliseconds per traced call of the ring's dispatch: ``vmcu.ring``
with its ``vmcu.op`` spans, without any ``vmcu.sync`` inside it
(``spans.py``)."""

SPANS = ("vmcu.ring", "vmcu.op")


def read(record, trace=None):
    if not trace or not trace.get("spans") or not record["traced"]["calls"]:
        return None
    own = sum(trace["spans"].get(n, {}).get("self_s", 0.0) for n in SPANS)
    return 1e3 * own / record["traced"]["calls"]
