"""MCUNet-style module tables, as the vMCU paper (arXiv:2406.06542)
lists them in its Table 2 (MCUNet, Lin et al., arXiv:2007.10319).

``widths["modules"]`` rows are ``[name, hw, c_in, c_mid, c_out, k,
[s_pw1, s_dw, s_pw2]]``.  Each row is an inverted bottleneck: pw1 (relu)
-> k x k depthwise (relu) -> pw2 (linear), plus a residual add where the
row keeps its shape.  Where a row's input does not chain from the
previous output, a linear 1x1 adapter conv connects them: strided when
the resolution divides down, nearest-grid resampled otherwise.  An
average pool and a linear classifier close the net.
"""
from chipbench.reference import Builder


def layers(widths: dict) -> list[dict]:
    h, w, c = widths["input"]
    b = Builder(h, w, c)
    for i, (name, hw, c_in, c_mid, c_out, k, strides) in enumerate(
            widths["modules"]):
        ch, _, cc = b.shapes[-1]
        if (ch, cc) != (hw, c_in):
            s = max(1, round(ch / hw))
            if -(-ch // s) == hw:
                b.conv(f"T{i}", c_in, stride=s, relu=False)
            else:
                b.conv(f"T{i}", c_in, relu=False, resample=(hw, hw))
        mod_in = b.cur
        s1, s2, s3 = strides
        b.conv(f"{name}.pw1", c_mid, stride=s1)
        b.dw(f"{name}.dw", k=k, stride=s2)
        b.conv(f"{name}.pw2", c_out, stride=s3, relu=False)
        if c_in == c_out and strides == [1, 1, 1]:
            b.add(f"{name}.add", mod_in)
    b.head(widths["num_classes"])
    return b.layers
