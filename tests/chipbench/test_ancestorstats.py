"""``consensus.ancestor_hit_share`` from the ``Host stats:`` lines of a
canned log: one process, a line every 10 s, numbers small enough to
work out by hand."""

import pytest

from chipbench.readers import ancestorstats, hoststats

HEAD = "hotstuff_tpu.telemetry.hoststats Host stats: elapsed_s=1.000 cpu_user_s=1.000 cpu_sys_s=0.100 lag_samples=9 lag_mean_ms=2.000 lag_max_ms=7.000 gc2=0 gc2_s=0.0000 store_appends=100 store_records=700"
LOG = f"""\
2026-10-02T12:00:00.000Z [INFO] {HEAD} ancestor_hits=0 ancestor_misses=128
2026-10-02T12:00:10.000Z [INFO] {HEAD} ancestor_hits=900 ancestor_misses=228
2026-10-02T12:00:20.000Z [INFO] {HEAD} ancestor_hits=3900 ancestor_misses=328
2026-10-02T12:00:30.000Z [INFO] {HEAD} ancestor_hits=9999 ancestor_misses=9999
"""
#: what a parent commit prints: the line without the two counters
PARENT = "\n".join(line.split(" ancestor_hits=")[0] for line in LOG.splitlines())
#: a committee that made no block: the counters stand still
STILL = "\n".join(
    line.split(" ancestor_hits=")[0] + " ancestor_hits=5 ancestor_misses=2"
    for line in LOG.splitlines()
)


class FakeRun:
    """What the readers touch of a ``reduce.Run``."""

    def __init__(self, text: str, after_first_s: float, seconds: float):
        self._host_stats = hoststats.lines_of(text)
        first = hoststats.lines_of(LOG)[0][0]
        self.t0 = first + after_first_s
        self.t1 = self.t0 + seconds


@pytest.mark.parametrize(
    "after_first_s, seconds, hits, misses",
    [
        (5.0, 20.0, 3900, 200),  # the line of :20 less the line of :00
        (12.0, 10.0, 3000, 100),  # the line of :20 less the line of :10
    ],
    ids=["two-lines-apart", "one-line-apart"],
)
def test_hits_over_lookups_of_the_window(after_first_s, seconds, hits, misses):
    run = FakeRun(LOG, after_first_s, seconds)
    assert ancestorstats.hit_share(run) == pytest.approx(
        100.0 * hits / (hits + misses)
    )


@pytest.mark.parametrize(
    "text, after_first_s, seconds",
    [
        (PARENT, 5.0, 20.0),  # a parent commit counts no lookups
        (STILL, 5.0, 20.0),  # no lookup in the window
        ("", 5.0, 20.0),  # no line at all
        (LOG.splitlines()[0] + "\n", 5.0, 20.0),  # one line is no difference
        (LOG, 12.0, 5.0),  # no line printed between start and end
    ],
    ids=["parent", "no-lookups", "empty", "one-line", "no-line-in-window"],
)
def test_nothing_to_read_is_none_and_never_raises(text, after_first_s, seconds):
    assert ancestorstats.hit_share(FakeRun(text, after_first_s, seconds)) is None
