"""The yardstick's operation and byte counts, against figures on record."""

import pytest

from benchmark import opcount


def test_hist_level_reproduces_the_roadmaps_floor():
    flops, nbytes = opcount.hist_level(2_000_000, 28, 255)
    assert flops == pytest.approx(8.6e10, rel=0.01)
    assert nbytes == 88_000_000
    peak = opcount.peaks("TPU v5 lite")
    seconds, bound = opcount.least_seconds(flops, nbytes, peak)
    assert bound == "compute" and seconds == pytest.approx(0.44e-3, rel=0.02)


def test_resnet50_multiply_adds():
    # He et al. 2015, table 1: 3.8e9 with the stride in the first 1x1
    assert opcount.resnet50_macs(stride_on_3x3=False) == pytest.approx(
        3.8e9, rel=0.02)
    # the graph that is run strides in the 3x3 (torchvision: 4.09 GMACs)
    assert opcount.resnet50_macs() == pytest.approx(4.09e9, rel=0.002)
    assert opcount.resnet50_flops(128) == 2 * 128 * opcount.resnet50_macs()
    shapes = opcount.resnet50_conv_shapes()
    assert len(shapes) == 53 + 1          # 53 convolutions and the classifier
    params = sum(cin * cout * k * k for cin, cout, k, _ in shapes)
    assert params == pytest.approx(25.5e6, rel=0.01)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        opcount.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        opcount.peaks("cpu")
