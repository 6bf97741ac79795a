"""Least time of the stem's work completed in the traced window over the
device time of the streaming state kernel (ops named
``ring_conv_stream*``): the kernel's own share of its roofline.  The
stem is the first layer, the one a streaming compile runs as a
``conv_stream``.  ``None`` where no such kernel ran (the ``jnp``
executor, or a net without stream state)."""
from chipbench.work import roofline_share


def read(record, trace=None):
    if not trace:
        return None
    stem = {**record, "least_layers": record["least_layers"][:1]}
    return roofline_share(stem, trace, ("ring_conv_stream",))
