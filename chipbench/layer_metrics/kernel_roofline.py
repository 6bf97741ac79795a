"""Least time of the work completed in the traced window over the
device time of the Pallas ring kernels (ops named ``ring_*``): the
kernels' own share of their roofline, without the whole-pool casts
around them that ``ring_roofline`` counts in the busy time.  ``None``
where no ring kernel ran (the ``jnp`` executor)."""
from chipbench.work import roofline_share


def read(record, trace=None):
    return roofline_share(record, trace, ("ring_",))
