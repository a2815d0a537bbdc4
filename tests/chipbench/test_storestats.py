"""``store.records_per_append`` from the ``Host stats:`` lines of a
canned log: one process, a line every 10 s, numbers small enough to
work out by hand."""

import pytest

from chipbench.readers import hoststats, storestats

HEAD = "hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=1.000 cpu_user_s=1.000 cpu_sys_s=0.100 lag_samples=9 lag_mean_ms=2.000 lag_max_ms=7.000 gc2=0 gc2_s=0.0000"
LOG = f"""\
2026-10-01T12:00:00.000Z [INFO] {HEAD} store_appends=100 store_records=100
2026-10-01T12:00:10.000Z [INFO] {HEAD} store_appends=300 store_records=1300
2026-10-01T12:00:20.000Z [INFO] {HEAD} store_appends=700 store_records=4300
2026-10-01T12:00:30.000Z [INFO] {HEAD} store_appends=9999 store_records=9999
"""
#: what a parent commit prints: the line without the two counters
PARENT = "\n".join(line.split(" store_appends=")[0] for line in LOG.splitlines())


class FakeRun:
    """What the readers touch of a ``reduce.Run``."""

    def __init__(self, text: str, after_first_s: float, seconds: float):
        self._host_stats = hoststats.lines_of(text)
        first = hoststats.lines_of(LOG)[0][0]
        self.t0 = first + after_first_s
        self.t1 = self.t0 + seconds


def test_records_over_appends_of_the_window():
    # the window 12:00:05 to 12:00:25: the line of :20 less the line of :00
    run = FakeRun(LOG, 5.0, 20.0)
    assert storestats.records_per_append(run) == pytest.approx(4200 / 600)
    # 12:00:12 to 12:00:22: the line of :20 less the line of :10
    run = FakeRun(LOG, 12.0, 10.0)
    assert storestats.records_per_append(run) == pytest.approx(3000 / 400)


@pytest.mark.parametrize(
    "text, after_first_s, seconds",
    [
        (PARENT, 5.0, 20.0),  # a parent commit counts no appends
        ("", 5.0, 20.0),  # no line at all
        (LOG.splitlines()[0] + "\n", 5.0, 20.0),  # one line is no difference
        (LOG, 12.0, 5.0),  # no line printed between start and end
    ],
    ids=["parent", "empty", "one-line", "no-line-in-window"],
)
def test_nothing_to_read_is_none_and_never_raises(text, after_first_s, seconds):
    assert storestats.records_per_append(FakeRun(text, after_first_s, seconds)) is None
