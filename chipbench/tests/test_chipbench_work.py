"""Work counts from layer shapes, independent of the ring layout."""
import json

import pytest

from chipbench_testlib import NETS, ROOT
from chipbench import common, work

import repro
from repro.obs.counters import op_macs

IMAGENET = json.loads((ROOT / "chipbench" / "configs" /
                       "mcunet-320kb-imagenet-m7.json").read_text())
ZOO = {**NETS, IMAGENET["net"]: IMAGENET}


def _program_layers(program):
    """The op shapes a compiled program runs, as reference-style layers."""
    kinds = {"conv_pw": "conv", "conv_k2d": "conv", "conv_dw": "dw",
             "add": "add", "pool_avg": "avgpool", "gemm": "fc"}
    out = []
    for op in program.ops:
        kind = kinds[op.kind]
        h, w = (op.h_in, op.w_in) if op.h_in else (op.rows_in or 1, 1)
        ho, wo = (op.h_out, op.w_out) if op.h_out else (op.rows_out or 1, 1)
        out.append(dict(kind=kind, h=h, w=w, c_in=op.d_in, c_out=op.d_out,
                        k=op.rs or 1, h_out=ho, w_out=wo))
    return out


@pytest.mark.parametrize("net", sorted(ZOO))
def test_macs_equal_the_programs_nominal_macs(net):
    layers = common.net_layers(ZOO[net])
    cn = repro.compile(net, "host-sim", dtype="int8", quantize=False,
                       certify=False, lint=False)
    want = sum(op_macs(op, cn.program.m_rows) for op in cn.program.ops)
    assert sum(work.macs(layer) for layer in layers) == want
    common.check_program(cn.program, layers)


@pytest.mark.parametrize("net", ["ds-cnn", "resnet-8"])
def test_dense_bytes_do_not_depend_on_ring_geometry(net):
    counts = []
    for seg_width, block_rows in ((128, 1), (16, None)):
        cn = repro.compile(net, "host-sim", dtype="int8", quantize=False,
                           certify=False, lint=False, seg_width=seg_width,
                           block_rows=block_rows)
        layers = _program_layers(cn.program)
        counts.append((sum(work.act_bytes(lr) for lr in layers),
                       sum(work.param_bytes(lr) for lr in layers),
                       cn.program.pool_bytes))
    (a0, p0, pool0), (a1, p1, pool1) = counts
    assert (a0, p0) == (a1, p1) and pool0 != pool1
    ref_layers = common.net_layers(ZOO[net])
    assert a0 == sum(work.act_bytes(lr) for lr in ref_layers)
    assert p0 == sum(work.param_bytes(lr) for lr in ref_layers)


def test_peaks_by_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            work.peaks(kind)


def test_least_time_takes_the_larger_bound():
    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    fc = dict(name="fc", kind="fc", h=1, w=1, c_in=100, c_out=10, h_out=1,
              w_out=1)
    pw = dict(name="pw", kind="conv", k=1, h=64, w=64, c_in=64, c_out=64,
              h_out=64, w_out=64)
    (_, t_fc, b_fc), (_, t_pw, b_pw) = work.least_time([fc, pw], 8, peak)
    assert b_fc == "memory" and b_pw == "memory"
    assert t_fc == pytest.approx((8 * 110 + 1000 + 120) / 1e9)
    big = dict(pw, c_in=4096, c_out=4096)
    (_, t_big, b_big), = work.least_time([big], 1, peak)
    assert b_big == "compute"
    assert t_big == pytest.approx(2 * 64 * 64 * 4096 * 4096 / 1e12)
