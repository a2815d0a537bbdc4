"""``trace.py`` on a small recorded trace: busy union, custom-call
totals, gaps.  ``data/trace_events.json`` is the ``XLA Ops`` line of
``/device:TPU:0`` for three verify waves, recorded on the v5e (see the
file's ``recorded``); the modules that held those operations took
4,681 + 253,209 + 4,643 + 252,982 + 4,636 + 253,315 ns by the same
trace's ``XLA Modules`` line."""

import json
import os

import pytest

from chipbench import trace
from chipbench.readers import kernel
from chipbench.reduce import Run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
with open(os.path.join(DATA, "trace_events.json")) as f:
    DEVICES = json.load(f)["devices"]
MODULES_NS = 4681 + 253209 + 4643 + 252982 + 4636 + 253315


def test_operation_names():
    assert trace.op_name(
        "%copy-start.3 = (s32[128]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(s32[128] %idxs.1)"
    ) == "copy-start.3"
    assert trace.op_name(
        "%verify_compressed.1 = s32[1,128]{1,0} custom-call(f32[80,256]{1,0} %copy-done.1)"
    ) == "verify_compressed.1_custom-call"


def test_busy_union_on_overlapping_intervals():
    events = [["%a = x", 0, 100], ["%b = x", 50, 100], ["%c = x", 400, 10],
              ["%d = x", 402, 2], ["%a = x", 1000, 5]]
    out = trace.summarise(events, 1.0)
    assert out["busy_s"] == pytest.approx((150 + 10 + 5) / 1e9)
    assert out["device_ops"][0] == ["a", pytest.approx(105 / 1e9)]
    assert out["idle_gaps"][:2] == [
        ["ends_at_a", pytest.approx(590 / 1e9)],
        ["ends_at_c", pytest.approx(250 / 1e9)],
    ]
    assert out["kernel_calls"] == 0 and out["kernel_s"] == 0


def test_recorded_trace():
    out = trace.summarise_devices(DEVICES, 0.6)
    assert out["window_s"] == 0.6
    # the operations fill their modules but for the gaps between them
    assert out["busy_s"] == pytest.approx(771_999 / 1e9)
    assert 0.99 * MODULES_NS < out["busy_s"] * 1e9 <= MODULES_NS
    assert out["kernel_calls"] == 3
    assert out["kernel_s"] == pytest.approx(753_888 / 1e9)
    name, seconds = out["device_ops"][0]
    assert name == "verify_compressed.1_custom-call"
    assert seconds == out["kernel_s"]
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    # the long gaps are the waits for the next round's wave
    assert out["idle_gaps"][0] == ["ends_at_copy-start.3", pytest.approx(0.173190281)]
    assert out["idle_gaps"][1][1] == pytest.approx(0.099493952)
    assert trace.summarise_devices([], 1.0) is None


def test_kernel_readers():
    run = Run({}, {}, None, None, [], set(), 0.0)
    assert kernel.wave_us(run) is None and kernel.waves_per_s(run) is None
    run.trace = trace.summarise_devices(DEVICES, 0.6)
    assert kernel.wave_us(run) == pytest.approx(251.296)
    assert kernel.waves_per_s(run) == pytest.approx(5.0)
    # four chips: busy is the mean over the devices
    both = trace.summarise_devices(DEVICES + [[]], 0.6)
    assert both["busy_s"] == pytest.approx(771_999 / 2e9)
