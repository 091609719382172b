"""The benchmark's yardstick on the CPU: the FLOP counter against the
program's zero-free MAC accounting, and the traffic generator."""
from __future__ import annotations

import numpy as np
import pytest

from bench import flops, harness, traffic


@pytest.mark.parametrize("batch,o,k,cin,cout,stride,dilation", [
    (2, 4, 4, 3, 8, 2, 1), (3, 8, 4, 16, 8, 2, 1), (1, 9, 3, 8, 4, 1, 3),
    (4, 5, 1, 12, 7, 1, 1)])
def test_conv_macs_match_program_accounting(batch, o, k, cin, cout, stride,
                                            dilation):
    """The benchmark counts the same useful MACs as the program's own
    zero-free accounting, for every op of a layer."""
    from repro.core import dataflow_sim as ds
    layer = ds.ConvLayer(name="l", c_in=cin, n_in=stride * (o - 1) + k,
                         n_out=o, k=k, m=cout, stride=stride, batch=batch,
                         dilation=dilation)
    mine = flops.conv_macs(batch, (o, o), k, cin, cout)
    for op in ("forward", "input_grad", "filter_grad", "dilated_forward"):
        assert mine == ds.useful_macs(layer, op)


def test_tconv_macs_match_zero_free_mapping():
    """A transposed conv's count equals the products of the paper's
    zero-free mapping (one per filter tap and error element), per
    channel pair and image."""
    from repro.core import mapping
    err_n, k, s = 4, 4, 2
    m = mapping.build_tconv_mapping(err_n, k, s)
    assert flops.conv_macs(1, (err_n, err_n), k, 1, 1) == m.n_useful_macs


def test_generator_and_aspp_counts():
    dcgan = {"kind": "dcgan", "z_dim": 100, "base": 256, "channels": 3}
    ops = {o.name: o for o in harness.load_model("dcgan").ops(dcgan, 64)}
    assert ops["t1"].flops == 2 * 64 * 16 * 16 * 256 * 512
    assert ops["t3"].flops == 2 * 64 * 256 * 16 * 3 * 128
    assert ops["proj"].kernel == "xla" and ops["t2"].kernel == "pallas"
    aspp = {"kind": "aspp", "feature_hw": [33, 33], "in_ch": 2048,
            "width": 256, "n_classes": 21, "rates": [6, 12, 18]}
    aspp_ops = harness.load_model("aspp").ops
    total = flops.total_flops(aspp_ops(aspp, 8))
    assert total == pytest.approx(247.1e9, rel=1e-3)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    branch = aspp_ops(aspp, 8)[0]
    # compute-bound: the roofline time is the FLOP time
    assert flops.roofline_s([branch], peaks) == branch.flops / 197e12


def test_open_schedule_same_work_every_seed():
    tr = {"loop": "open", "rate_per_s": 300.0, "payload_pool": 32}
    a = traffic.open_schedule(tr, 2 ** 31 + 7, 10.0)
    b = traffic.open_schedule(tr, 11, 10.0)
    assert len(a) == len(b) == 3000
    assert np.all(np.diff(a) >= 0) and a[0] == 0.0 and a[-1] < 10.0
    # same multiset of gaps, another order
    gaps = lambda t: np.sort(np.diff(np.append(t, 10.0)))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, traffic.open_schedule(
        tr, 2 ** 31 + 7, 10.0))
    # every second holds the same 300 arrivals, on every seed
    for t in (a, b):
        np.testing.assert_allclose(t[::300], np.arange(10.0), atol=1e-9)
        seconds = [np.sort(np.diff(np.append(t[k:k + 300], k / 300 + 1)))
                   for k in range(0, 3000, 300)]
        np.testing.assert_allclose(seconds[0], seconds[-1], rtol=1e-9)


def test_payload_order_uses_every_payload_equally():
    tr = {"payload_pool": 32}
    idx = traffic.payload_order(tr, 5, 3200)
    assert np.all(np.bincount(idx, minlength=32) == 100)
    assert not np.array_equal(idx, traffic.payload_order(tr, 6, 3200))
