"""MobileNetV1 (Howard et al., arXiv:1704.04861) at a width multiplier.

A 3x3 stride-2 stem (relu) and depthwise-separable blocks of
``[channels, stride]`` (3x3 depthwise relu, 1x1 pointwise relu), channels
scaled by ``width_mult`` and rounded to a multiple of 8 (at least 8);
then an average pool and a linear classifier.
"""
from chipbench.reference import Builder


def layers(widths: dict) -> list[dict]:
    h, w, c = widths["input"]
    mult = widths["width_mult"]

    def ch(n: int) -> int:
        return max(8, int(n * mult + 0.5) // 8 * 8)

    b = Builder(h, w, c)
    b.conv("stem", ch(widths["stem_channels"]), k=3, stride=2)
    for i, (n, stride) in enumerate(widths["blocks"]):
        b.dw(f"B{i}.dw", stride=stride)
        b.conv(f"B{i}.pw", ch(n))
    b.head(widths["num_classes"])
    return b.layers
