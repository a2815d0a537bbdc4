"""The host's side of a traced window: ``chipbench/hostspans.py`` on a
recorded host-and-device event file (``data/host_events.json``: one
loop thread, one slot thread, one device; times in whole microseconds
so every expected number can be worked out by hand), and the readers
that hand its numbers to ``run.py``."""

import json
import os

import pytest

from chipbench import hostspans
from chipbench.readers import hostspans as reader
from chipbench.readers import hoststats

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000  # the file's unit, in the profiler's nanoseconds


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(DATA, "host_events.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(events):
    return hostspans.reduce(events)


def test_self_time_is_a_spans_time_less_its_children(events):
    loop = max(events["threads"], key=len)
    own = {
        (e[0], e[1]): ns for e, ns in hostspans.self_times(loop)
    }
    # core.commit [2600, 3500) holds store.apply [2800, 3200)
    assert own[("core.commit", 2600 * US)] == 500 * US
    assert own[("store.apply", 2800 * US)] == 400 * US
    # a span with no child keeps all of its time
    assert own[("core.proposal", 1500 * US)] == 900 * US


def test_self_time_of_nested_children_two_deep():
    thread = [
        ["a", 0, 100, {}], ["b", 10, 50, {}], ["c", 20, 10, {}],
        ["d", 70, 20, {}], ["e", 200, 5, {}],
    ]
    own = {e[0]: ns for e, ns in hostspans.self_times(thread)}
    assert own == {"a": 30, "b": 40, "c": 10, "d": 20, "e": 5}


def test_the_round_is_split_by_layer_over_the_rounds_begun(reduced):
    # proposer.make spans carry round=5 and round=6: two rounds begun
    assert reduced["rounds"] == 2
    assert reduced["layer_ms_per_round"] == pytest.approx({
        "consensus": 1.0,  # make 400+200, proposal 900, commit 900-400
        "store": 0.25,  # apply 400 + write 100
        "network": 0.1,
        "ingest": 0.05,
        "verify": 0.25,  # 3 submits, collect, route, pack, spawn, deliver
    })
    assert reduced["spans_per_round"] == 8.5  # 17 spans that are not idle


def test_loop_idle_and_what_no_span_covers(reduced):
    # 8 ms window, loop.idle 3 x 1 ms; spans cover 3.3 of the 5 busy ms
    assert reduced["window_s"] == pytest.approx(0.008)
    assert reduced["loop_idle_share"] == pytest.approx(37.5)
    assert reduced["loop_unspanned_share"] == pytest.approx(34.0)
    layers = sum(reduced["layer_ms_per_round"].values())
    assert (
        layers
        + reduced["loop_unspanned_ms_per_round"]
        + reduced["loop_idle_ms_per_round"]
    ) == pytest.approx(reduced["window_s"] * 1e3 / reduced["rounds"])


def test_a_waves_waits_are_joined_by_its_serial(reduced):
    # wave 1 is whole; wave 2 has only its submit inside the trace
    assert (reduced["waves"], reduced["waves_seen"]) == (1, 2)
    assert reduced["wave_ms"] == pytest.approx({
        "e2e": 2.2,  # first submit 4400 -> end of deliver 6600
        "coalesce": 0.6,  # first submit 4400 -> collect 5000
        "staging": 0.43,  # pack 80 + flatten 50 + prepare 300
        "device_call": 0.6,  # dispatch 100 + execute 400 + readback 100
        "handoff": 0.25,  # spawn end 5250 -> wall 5400; wall end 6400 -> 6500
        "loop_other": 0.27,  # collect 100, route 20, spawn 50, deliver 100
        "accounted": 2.15,
    })
    assert reduced["wave_ms"]["accounted"] >= 0.9 * reduced["wave_ms"]["e2e"]


def test_device_idle_time_is_split_by_what_the_host_did(reduced):
    # device busy 250 + 40 of 8000; wave 1 open over [4400, 6600)
    assert reduced["device_idle_s"] == pytest.approx(7710e-6)
    assert reduced["idle_wave_in_flight_share"] == pytest.approx(
        100 * (2200 - 290) / 7710
    )
    # no wave open: [0, 4400) and [6600, 8000); spans cover 2600 + 250
    assert reduced["idle_loop_busy_share"] == pytest.approx(
        100 * 2850 / 7710
    )
    assert (
        reduced["idle_wave_in_flight_share"]
        + reduced["idle_loop_busy_share"]
    ) <= 100


def test_the_kernel_starts_inside_its_waves_device_call(reduced):
    # one clock: dispatch starts at 5760, device.execute ends at 6260,
    # the device's verify_compressed event starts at 5900
    assert reduced["kernel_events"] == 1
    assert reduced["kernel_events_inside_their_wave"] == 1
    assert reduced["kernel_events_inside_their_frame"] == 1
    assert reduced["kernel_start_after_dispatch_us"] == [140.0] * 3


def test_a_kernel_event_past_the_host_window_is_not_the_windows(events):
    """The device's tracer stops after the host's: a kernel event that
    starts after the last span belongs to a wave the trace does not
    hold, and a skewed one that starts before its ``dispatch`` is
    counted in the frame and not in the call."""
    late = {
        "threads": events["threads"],
        "device": events["device"] + [
            [events["device"][0][0], 9000 * US, 250 * US],
        ],
    }
    assert hostspans.reduce(late)["kernel_events"] == 1
    skewed = {
        "threads": events["threads"],
        "device": [[events["device"][0][0], 5700 * US, 250 * US]],
    }
    out = hostspans.reduce(skewed)
    assert out["kernel_events_inside_their_wave"] == 0
    assert out["kernel_events_inside_their_frame"] == 1
    assert out["kernel_start_after_dispatch_us"][0] == -60.0


def test_idle_gaps_are_named_by_what_the_host_was_doing(reduced):
    first, second = reduced["idle_gaps"][:2]
    assert first["seconds"] == pytest.approx(5900e-6)
    assert first["loop"][0] == "loop.idle"
    # the slot thread's stage, not the frame around the stages
    assert first["slot"][:2] == ["prepare", pytest.approx(300e-6)]
    assert first["slot"][2]["wave"] == 1
    assert second["seconds"] == pytest.approx(1800e-6)
    assert second["slot"][0] == "readback"


def test_interval_arithmetic():
    a = hostspans.union([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert a == [(0, 4), (5, 9)]
    assert hostspans.length(a) == 8
    assert hostspans.subtract([(0, 10)], a) == [(4, 5), (9, 10)]
    assert hostspans.subtract(a, [(1, 6), (8, 20)]) == [(0, 1), (6, 8)]
    assert hostspans.overlap(a, [(3, 6), (8, 12)]) == 3


def test_a_trace_without_the_programs_spans_reduces_to_nothing():
    """A parent commit's trace: device events and no span."""
    assert hostspans.reduce({"threads": [], "device": [["x", 0, 5]]}) is None
    assert hostspans.reduce({}) is None


def test_a_loop_that_never_waits_is_still_found(events):
    """A saturated loop's ``select`` never takes a timeout, so the
    trace has no ``loop.idle``: the loop thread is the one with the
    layers' spans, and it reads 0% idle."""
    busy = {
        "threads": [
            [e for e in t if e[0] != hostspans.IDLE]
            for t in events["threads"]
        ],
        "device": events["device"],
    }
    out = hostspans.reduce(busy)
    assert out["loop_idle_share"] == 0.0
    assert out["layer_ms_per_round"]["consensus"] == pytest.approx(1.0)


class FakeRun:
    """What the readers take of a ``Run``."""

    def __init__(self, config="colo64", traffic="low-colo64"):
        self.config = {"name": config}
        self.traffic = {"name": traffic}
        self.t0, self.t1 = 1000.0, 1051.0


def test_the_reader_finds_the_cells_run_directory():
    assert reader.run_dir_of(FakeRun()) == os.path.join(
        reader.ROOT, "chiprun_out", "chipbench", "colo64.low"
    )
    assert reader.run_dir_of(FakeRun(traffic="no-such-mix")) is None


READERS = [
    "core_ms_per_round", "network_ms_per_round", "store_ms_per_round",
    "ingest_ms_per_round", "verify_loop_ms_per_round", "loop_idle_share",
    "loop_unspanned_share", "wave_e2e_ms", "wave_coalesce_ms",
    "wave_staging_ms", "wave_device_call_ms", "wave_handoff_ms",
    "idle_wave_in_flight_share", "idle_loop_busy_share",
]


@pytest.mark.parametrize("name", READERS)
def test_each_span_reader_hands_on_its_number(name, reduced):
    run = FakeRun()
    run._host_spans = reduced  # as read once from the run's trace
    expected = {
        "core_ms_per_round": 1.0, "network_ms_per_round": 0.1,
        "store_ms_per_round": 0.25, "ingest_ms_per_round": 0.05,
        "verify_loop_ms_per_round": 0.25, "loop_idle_share": 37.5,
        "loop_unspanned_share": 34.0, "wave_e2e_ms": 2.2,
        "wave_coalesce_ms": 0.6, "wave_staging_ms": 0.43,
        "wave_device_call_ms": 0.6, "wave_handoff_ms": 0.25,
        "idle_wave_in_flight_share": 100 * 1910 / 7710,
        "idle_loop_busy_share": 100 * 2850 / 7710,
    }[name]
    assert getattr(reader, name)(run) == pytest.approx(expected)
    # an untraced run, or a parent commit's trace: nothing, not an error
    bare = FakeRun()
    bare._host_spans = None
    assert getattr(reader, name)(bare) is None


def test_an_untraced_run_has_no_trace_to_read():
    assert reader.reduced(FakeRun(traffic="no-such-mix")) is None


HOST_LOG = """\
2026-09-30T12:00:00.000Z [INFO] hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=5.000 cpu_user_s=10.000 cpu_sys_s=1.000 lag_samples=90 lag_mean_ms=2.000 lag_max_ms=700.000 gc2=0 gc2_s=0.0000
2026-09-30T12:00:05.000Z [INFO] hotstuff_tpu.consensus.core.abcdefgh Committed block 7 -> xyz
2026-09-30T12:00:10.000Z [INFO] hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=15.000 cpu_user_s=19.000 cpu_sys_s=2.000 lag_samples=270 lag_mean_ms=2.100 lag_max_ms=41.500 gc2=0 gc2_s=0.0000
2026-09-30T12:00:20.000Z [INFO] hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=25.000 cpu_user_s=28.500 cpu_sys_s=3.500 lag_samples=450 lag_mean_ms=2.200 lag_max_ms=133.250 gc2=1 gc2_s=0.0380
2026-09-30T12:00:30.000Z [INFO] hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=35.000 cpu_user_s=40.000 cpu_sys_s=5.000 lag_samples=630 lag_mean_ms=2.300 lag_max_ms=900.000 gc2=2 gc2_s=0.0800
"""


def test_host_stats_lines_are_read_as_last_less_first():
    lines = hoststats.lines_of(HOST_LOG)
    assert len(lines) == 4 and lines[1][0] - lines[0][0] == 10.0
    run = FakeRun()
    run._host_stats = lines
    # the window: 12:00:05 to 12:00:25
    run.t0 = lines[0][0] + 5.0
    run.t1 = lines[0][0] + 25.0
    # last line at or before t1 (12:00:20) less the last at or before t0
    # (12:00:00): 18.5 + 2.5 CPU seconds in 20 s of wall
    assert hoststats.cpu_share(run) == pytest.approx(100 * 21.0 / 20.0)
    assert hoststats.gc_pause_ms(run) == pytest.approx(38.0)
    # the lag's max is of the 5 s before its line: the largest line
    # printed inside the window, not the 700 before it nor the 900 after
    assert hoststats.loop_lag_max_ms(run) == 133.25


def test_a_log_without_the_line_gives_nothing():
    run = FakeRun()
    run._host_stats = hoststats.lines_of(
        "2026-09-30T12:00:05.000Z [INFO] x Committed block 7 -> xyz\n"
    )
    assert hoststats.cpu_share(run) is None
    assert hoststats.loop_lag_max_ms(run) is None
    assert hoststats.gc_pause_ms(run) is None


# ---- a quarter of a second of a real traced run ---------------------------
#
# data/host_events_chip.json: 0.23 s cut from the traced ``colo64.low``
# run "t2" (my chip run, PR 26, TPU v5 lite), times rebased, each device
# operation's HLO text cut to its name.  Three threads (two slot
# threads and the event loop), two rounds begun, three whole waves.


@pytest.fixture(scope="module")
def chip():
    with open(os.path.join(DATA, "host_events_chip.json")) as f:
        return json.load(f)


def test_chip_excerpt_parts_add_up_to_the_window(chip):
    out = hostspans.reduce(chip)
    assert out["rounds"] == 2 and out["spans"] == 2545
    per_round = (
        sum(out["layer_ms_per_round"].values())
        + out["loop_unspanned_ms_per_round"]
        + out["loop_idle_ms_per_round"]
    )
    assert per_round * out["rounds"] == pytest.approx(out["window_s"] * 1e3)
    assert set(out["layer_ms_per_round"]) == {
        "consensus", "network", "store", "ingest", "verify"
    }
    assert 0 < out["loop_idle_share"] < 10  # a saturated loop
    assert 15 < out["loop_unspanned_share"] < 30


def test_chip_excerpt_waves_and_the_one_clock(chip):
    out = hostspans.reduce(chip)
    assert (out["waves"], out["waves_seen"]) == (3, 4)
    wave = out["wave_ms"]
    assert wave["accounted"] >= 0.9 * wave["e2e"]
    assert wave["coalesce"] > wave["staging"] + wave["device_call"]
    kernels = [
        e for e in chip["device"] if e[0].startswith("%verify_compressed")
    ]
    assert len(kernels) == out["kernel_events"] == 3
    # the host's call around the kernel is never shorter than the kernel
    assert wave["device_call"] >= max(d for _, _, d in kernels) / 1e6
    # every kernel event starts inside its wave's dispatch.wall frame,
    # and within a millisecond of its dispatch span's start
    assert out["kernel_events_inside_their_frame"] == 3
    first, _, last = out["kernel_start_after_dispatch_us"]
    assert -500 < first and last < 5000
    assert (
        out["idle_wave_in_flight_share"] + out["idle_loop_busy_share"] <= 100
    )


def test_chip_excerpt_every_stage_span_carries_its_wave(chip):
    """``dispatch.wall`` hands ``wave`` down to the stages inside it."""
    stages = [
        e for t in chip["threads"] for e in t if e[0] in hostspans.SLOT_STAGES
    ]
    assert len(stages) == 18
    assert all("wave" in e[3] for e in stages)
    on_loop = [
        e for t in chip["threads"] for e in t
        if e[0].startswith(("core.", "net.decode", "store.", "ingest."))
    ]
    assert all(len(e[3].get("node", "")) == 8 for e in on_loop
               if e[0] != "core.sign")
