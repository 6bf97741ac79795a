"""DS-CNN keyword spotting (MLPerf Tiny, Banbury et al., arXiv:2106.07597).

A ``k x k`` stride-``s`` stem conv (relu), ``blocks`` depthwise-separable
blocks of ``channels`` (3x3 depthwise relu, 1x1 pointwise relu), then an
average pool and a linear classifier.  ``widths["stem"]`` is the
stem as run (see the configuration's ``assumed``).
"""
from chipbench.reference import Builder


def layers(widths: dict) -> list[dict]:
    h, w, c = widths["input"]
    b = Builder(h, w, c)
    stem = widths["stem"]
    b.conv("stem", widths["channels"], k=stem["k"], stride=stem["stride"])
    for i in range(widths["blocks"]):
        b.dw(f"B{i}.dw")
        b.conv(f"B{i}.pw", widths["channels"])
    b.head(widths["num_classes"])
    return b.layers
