"""What the loop ran between the layer spans: ``chipbench/readers/
loopcalls.py`` on a hand-built events file (``data/loop_events.json``:
the loop thread's ``loop.idle``, ``cb.*`` spans holding layer spans,
gaps between the callbacks, a layer span outside every callback, and
one device; times in whole microseconds so every number can be worked
out by hand), and ``host.loop_cpu_share`` from the ``Host stats:``
line."""

import json
import os

import pytest

from chipbench import hostspans
from chipbench import loopcalls as loopcalls_extract
from chipbench.readers import hoststats, loopcalls, loopcpu

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(DATA, "loop_events.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(events):
    return loopcalls.reduce(events)


def _as_hostspans_reads(events, keep):
    return hostspans.reduce({
        "threads": [[e for e in events["loop"] if keep(e[0])]],
        "device": events["device"],
    })


class FakeRun:
    def __init__(self, r):
        self._loop_calls = r


@pytest.mark.parametrize(
    "reader, expected",
    [
        # Core.run 1000 - 700 + Receiver 800 - 500 + Core.run 600 - 200
        ("task_ms_per_round", 0.5),
        ("io_ms_per_round", 0.4),  # read_ready 500 - 200, write_ready 500
        ("timer_ms_per_round", 0.1),  # 300 - net.write 100
        ("call_ms_per_round", 0.1),  # set_result 200, nothing inside
        # busy, in no span: 100 + 100 + 200 + 100 + 500 us
        ("machinery_ms_per_round", 0.5),
        ("callbacks_per_round", 3.5),  # seven callbacks, two rounds
    ],
)
def test_each_span_metric_as_worked_out_by_hand(reduced, reader, expected):
    assert getattr(loopcalls, reader)(FakeRun(reduced)) == pytest.approx(
        expected
    )


def test_the_five_parts_sum_to_what_no_span_covers(events, reduced):
    host = _as_hostspans_reads(
        events, lambda n: hostspans.layer_of(n) is not None
    )
    parts = (
        sum(reduced["kind_ms_per_round"].values())
        + reduced["machinery_ms_per_round"]
    )
    # 8.5 ms window, 3.5 idle, 1.8 under layer spans: 3.2 ms, two rounds
    assert host["loop_unspanned_ms_per_round"] == pytest.approx(1.6)
    assert parts == pytest.approx(host["loop_unspanned_ms_per_round"])
    assert reduced["unspanned_ms_per_round"] == pytest.approx(parts)
    assert reduced["rounds"] == host["rounds"] == 2
    assert reduced["window_s"] == pytest.approx(host["window_s"])


def test_a_layer_span_outside_every_callback_is_reported(reduced):
    # store.read [5200, 5300) lies between two callbacks
    assert reduced["spans_outside_callbacks_ms_per_round"] == pytest.approx(
        0.05
    )
    assert reduced["spans_outside_callbacks_share"] == pytest.approx(2.0)


def test_the_top_callbacks_by_self_time(reduced):
    top = [(t["kind"], t["name"], t["count"]) for t in reduced["top"]]
    assert top[0] == ("cb.task", "Core.run", 2)
    assert reduced["top"][0]["self_ms_per_round"] == pytest.approx(0.35)
    assert len(top) == 6


def test_a_device_idle_gap_is_split_by_what_the_loop_ran(reduced):
    first, second = reduced["idle_gaps"]
    # [4150, 7300): the device idle between two operations
    assert first["ms"] == pytest.approx(3.15)
    assert first["callback"] == {
        "kind": "cb.task", "name": "Receiver._serve",
        "ms": pytest.approx(0.8),
    }
    assert first["by_kind_ms"] == pytest.approx(
        {"cb.call": 0.05, "cb.task": 0.7, "cb.io": 0.3}
    )
    assert first["by_layer_ms"] == pytest.approx(
        {"store": 0.3, "consensus": 0.5}
    )
    assert (first["idle_ms"], first["machinery_ms"]) == pytest.approx(
        (1.0, 0.3)
    )
    for gap in (first, second):  # a partition of the gap
        assert (
            sum(gap["by_kind_ms"].values()) + sum(gap["by_layer_ms"].values())
            + gap["idle_ms"] + gap["machinery_ms"]
        ) == pytest.approx(gap["ms"])
    assert second["callback"]["name"] == "Core.run"


def test_a_trace_without_callback_spans_gives_none(events):
    parent = {
        "loop": [e for e in events["loop"] if not e[0].startswith("cb.")],
        "device": events["device"],
    }
    assert loopcalls.reduce(parent) is None
    assert loopcalls.reduce({"loop": [], "device": []}) is None
    run = FakeRun(None)
    for reader in ("task_ms_per_round", "io_ms_per_round",
                   "timer_ms_per_round", "call_ms_per_round",
                   "machinery_ms_per_round", "callbacks_per_round"):
        assert getattr(loopcalls, reader)(run) is None


def test_hostspans_drops_every_callback_span():
    # the annotation itself (``cb``) and the names it is read under
    for name in ("cb", "cb.task", "cb.io", "cb.timer", "cb.call"):
        assert hostspans.layer_of(name) is None


@pytest.mark.parametrize("kind", ["task", "io", "timer", "call"])
def test_a_callback_is_named_by_its_kind(kind):
    stats = [("kind", kind), ("name", "Core.run"), ("wave", 3)]
    assert loopcalls_extract.event_of("cb", 10, 5, stats) == [
        f"cb.{kind}", 10, 5, {"name": "Core.run"}
    ]
    assert loopcalls_extract.kept("cb")
    assert not loopcalls_extract.kept("cb.task")


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 0, 2)])
def test_both_extractors_pick_the_same_loop_thread(order):
    """On several threads (a slot thread with the most events, a second
    thread with layer spans, the loop) ``loopcalls.loop_thread`` takes
    the thread ``hostspans.reduce`` takes: the windows agree."""
    def made(r):
        return ["proposer.make", r * 100, 10, {"round": r}]

    slot = [["flatten", i, 1, {}] for i in range(50)]
    other = [made(r) for r in range(3)]
    loop = [made(r) for r in range(5, 10)] + [["cb.task", 500, 20, {}]]
    threads = [[slot, other, loop][i] for i in order]
    picked = loopcalls_extract.loop_thread(threads)
    assert picked is loop
    host = hostspans.reduce({
        "threads": [[e for e in t if hostspans.layer_of(e[0])]
                    for t in threads],
        "device": [],
    })
    assert host["rounds"] == 5
    assert loopcalls.reduce({"loop": picked, "device": []})["rounds"] == 5


def test_on_a_real_trace_both_extractors_agree(tmp_path):
    """A profiler trace of the node's loop on the CPU, a second thread
    with layer spans and a slot thread beside it: both extractors take
    the same loop thread, every callback is named by its kind, and the
    five parts sum to what no layer span covers."""
    import asyncio
    import threading
    import time

    import jax

    from hotstuff_tpu.node.main import _new_event_loop
    from hotstuff_tpu.telemetry import spans

    def beside(stages):
        for stage in stages:
            with spans.span(stage, round=0):
                time.sleep(0.0002)

    async def main():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        threads = [
            threading.Thread(target=beside, args=(["flatten"] * 40,)),
            threading.Thread(target=beside, args=(["net.decode"] * 3,)),
        ]
        for t in threads:
            t.start()
        for r in range(6):
            await asyncio.sleep(0.002)
            with spans.span("proposer.make", round=r):
                time.sleep(0.0005)
        for t in threads:
            t.join()
        await asyncio.sleep(0)
        jax.profiler.stop_trace()

    asyncio.run(main(), loop_factory=_new_event_loop)
    host = hostspans.reduce(hostspans.trace_events(str(tmp_path)))
    events = loopcalls_extract.loop_events(str(tmp_path))
    names = {e[0] for e in events["loop"]}
    assert {"cb.task", "cb.timer"} <= names and "cb" not in names
    ours = loopcalls.reduce(events)
    assert ours["rounds"] == host["rounds"] == 6
    assert ours["window_s"] == pytest.approx(host["window_s"])
    assert ours["unspanned_ms_per_round"] == pytest.approx(
        host["loop_unspanned_ms_per_round"]
    )


def test_hostspans_reads_what_it_read_before_the_callback_spans(events):
    """Filtered as ``trace_events`` filters, the file gives the layer
    and between-the-spans numbers of the same file without ``cb.*``."""
    kept = _as_hostspans_reads(
        events, lambda n: hostspans.layer_of(n) is not None
    )
    without = _as_hostspans_reads(events, lambda n: not n.startswith("cb."))
    for key in ("layer_ms_per_round", "loop_unspanned_ms_per_round",
                "loop_unspanned_share", "loop_idle_share", "rounds",
                "spans_per_round"):
        assert kept[key] == without[key]


HEAD = "hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=1.000 cpu_user_s=1.000 cpu_sys_s=0.100"
LOG = f"""\
2026-10-01T12:00:00.000Z [INFO] {HEAD} loop_cpu_s=10.000 lag_samples=9
2026-10-01T12:00:10.000Z [INFO] {HEAD} loop_cpu_s=18.000 lag_samples=9
2026-10-01T12:00:20.000Z [INFO] {HEAD} loop_cpu_s=27.500 lag_samples=9
"""


class StatsRun:
    def __init__(self, text: str, after_first_s: float, seconds: float):
        self._host_stats = hoststats.lines_of(text)
        first = hoststats.lines_of(LOG)[0][0]
        self.t0 = first + after_first_s
        self.t1 = self.t0 + seconds


@pytest.mark.parametrize(
    "text, expected",
    [
        (LOG, 100.0 * 17.5 / 20),  # the line of :20 less the line of :00
        (LOG.replace(" loop_cpu_s=", " other_s="), None),  # a parent's line
        ("", None),
    ],
    ids=["with", "without", "empty"],
)
def test_loop_cpu_share_from_the_stats_line(text, expected):
    got = loopcpu.loop_cpu_share(StatsRun(text, 5.0, 20.0))
    assert got == (pytest.approx(expected) if expected is not None else None)
