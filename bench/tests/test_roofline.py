"""Each roofline function from its shapes, and the share it yields."""
import pytest

from bench import rooflines, spec
from bench.roofline.shapes import result_and_operands
from bench.trace import Event, Trace

PROBE = ('%bloom_probe.3 = s32[2881536]{0:T(1024)S(1)} custom-call('
         'f32[128,128]{1,0:T(8,128)S(1)} %convert_bitcast_fusion.1, '
         's32[2881536]{0:T(1024)S(1)} %pad.13), custom_call_target='
         '"tpu_custom_call", operand_layout_constraints={f32[128,128]{1,0}, '
         's32[2881536]{0}}')
FRONTIER = ('%frontier_expand.7 = s32[118784]{0:T(1024)S(1)} custom-call('
            's32[5761024]{0:T(1024)S(1)} %pad.33, f32[5761024]{0:T(1024)S(1)}'
            ' %pad.34, s32[118784]{0:T(1024)S(1)} %pad_convert_fusion.2), '
            'custom_call_target="tpu_custom_call"')


def test_shapes_of_an_op():
    result, operands = result_and_operands(PROBE)
    assert result == ("s32", (2881536,))
    assert operands == [("f32", (128, 128)), ("s32", (2881536,))]


def test_bloom_probe_cost():
    ops, nbytes = spec.roofline("bloom_probe").cost(Event(PROBE, 0, 1), None)
    n = 2881536
    assert ops == n * 2 * 7
    assert nbytes == 4 * n + 128 * 128 // 8 + n


def test_frontier_expand_cost():
    ops, nbytes = spec.roofline("frontier_expand").cost(
        Event(FRONTIER, 0, 1), None)
    assert ops == 2 * 5761024
    assert nbytes == 5761024 * 10 + 118784 * 2


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.notes = []

    def peaks(self):
        return spec.peaks("TPU v5 lite")

    def roofline(self, kernel):
        return spec.roofline(kernel)


def test_share_is_least_time_over_device_time():
    # one call of 1 ms: least time = bytes / 819 GB/s (memory-bound)
    t = Trace(devices={"/device:TPU:0": [Event(FRONTIER, 0, 1_000_000)]},
              host=[], window=(0.0, 2e6))
    run = _Run(t)
    share = rooflines.share(run, "frontier_expand")
    least = (5761024 * 10 + 118784 * 2) / 819e9
    assert share == pytest.approx(100 * least / 1e-3)
    assert "memory-bound" in run.notes[0]


def test_no_kernel_call_reads_nothing():
    t = Trace(devices={"/device:TPU:0": [Event(PROBE, 0, 10)]}, host=[],
              window=(0.0, 20.0))
    assert rooflines.share(_Run(t), "frontier_expand") is None
    assert rooflines.share(_Run(None), "bloom_probe") is None
