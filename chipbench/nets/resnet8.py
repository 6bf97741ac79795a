"""ResNet-8 (MLPerf Tiny image classification, arXiv:2106.07597).

A 3x3 stem (relu) and residual stacks of ``[channels, stride]``: 3x3
conv (relu), 3x3 conv (linear), a linear 1x1 shortcut projection where
the stack changes shape, and a relu residual add; then an average pool
and a linear classifier.
"""
from chipbench.reference import Builder


def layers(widths: dict) -> list[dict]:
    h, w, c = widths["input"]
    b = Builder(h, w, c)
    b.conv("stem", widths["stem_channels"], k=3)
    for i, (ch, stride) in enumerate(widths["stacks"]):
        block_in = b.cur
        b.conv(f"R{i}.c1", ch, k=3, stride=stride)
        main = b.conv(f"R{i}.c2", ch, k=3, relu=False)
        if stride != 1 or b.shapes[block_in][2] != ch:
            b.conv(f"R{i}.sc", ch, stride=stride, relu=False, src=block_in)
            b.add(f"R{i}.add", main, relu=True)
        else:
            b.add(f"R{i}.add", block_in, relu=True)
    b.head(widths["num_classes"])
    return b.layers
