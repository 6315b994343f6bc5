"""The nominal work counts and the peaks table."""
import pytest

from bench import work


def test_chain_512_counts():
    shape = (512, 512, 512)
    assert work.real_transform(shape)["bytes"] == 1_075_838_976
    assert work.bandpass(shape)["bytes"] == 1_077_936_128
    assert work.chain_field(shape)["bytes"] == 3_229_614_080


def test_chain_1024_counts():
    assert work.chain_field((1024,) * 3)["bytes"] == 25_803_358_208


def test_ns2d_8192_step_counts():
    shape = (8192, 8192)
    assert work.real_transform(shape)["bytes"] == 536_936_448
    assert work.ns2d_step(shape)["bytes"] == 10_738_728_960


def test_roofline_is_bytes_bound_at_these_sizes():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.roofline_s(work.chain_field((512,) * 3), peak)
    assert bound == "hbm"
    assert t == pytest.approx(3_229_614_080 / 819e9)
    t4, _ = work.roofline_s(work.chain_field((1024,) * 3), peak, chips=4)
    assert t4 == pytest.approx(25_803_358_208 / 4 / 819e9)


def test_flops_bound_when_bytes_are_few():
    t, bound = work.roofline_s({"bytes": 0.0, "flops": 197e12},
                               work.peaks("TPU v5 lite"))
    assert (t, bound) == (pytest.approx(1.0), "flops")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
