"""What a BLS committee's aggregation did: the ``BLS stats:`` line.

One line a process every ``hoststats.LOG_INTERVAL`` seconds, printed
after the ``Host stats:`` line and only once the process has built a
BLS verifier or signing service (an ed25519 committee prints none).
Cumulative like the other stats lines: a reader takes the last line at
or before its window's end less the last at or before its start::

    BLS stats: signs=4096 device_adds=2752 host_adds=0 snapshots=64
      qcs=64 compact_qcs=64 agg_verifies=64 agg_failures=0 pairings=130
      native_signs=4096 device_calls=64

- ``signs``: signatures made (``BlsSigningService.sign_sync``, all
  nodes): votes, blocks and timeouts.
- ``native_signs``: of those, the ones the native library made in one
  call (``crypto/bls/native.py`` ``sign``); the rest were made in pure
  Python (``HOTSTUFF_BLS_NATIVE=0``, or no library).
- ``device_adds`` / ``host_adds``: vote signatures a QC maker added to
  its running sum on the device (``tpu/bls.py`` ``TpuG1RunningSum``)
  and on the host (a Jacobian add); the verifier the node was given
  decides which (``consensus/aggregator.py`` ``_SigAccumulator``).
- ``device_calls``: folds of staged votes dispatched to the device
  (``TpuG1RunningSum``: one when its buffer of ``FOLD_ROWS`` fills and
  one at a snapshot for what remains), the warm-up's included;
  ``device_adds`` over it is the votes a call carried.
- ``snapshots``: running sums read back from the device at quorum.
- ``qcs`` / ``compact_qcs``: QCs the leaders made, and of those the ones
  in the compact form (one aggregate signature and a signer bitmap).
- ``agg_verifies`` / ``agg_failures``: compact certificates checked
  (``BlsVerifier.verify_aggregate_msg``), and those that did not verify.
- ``pairings``: pairing equalities evaluated, native or pure Python, on
  any thread; a multi-pairing product counts once.

``chipbench/readers/bls.py`` reads the line.
"""

from __future__ import annotations

import threading

FIELDS = (
    "signs",
    "device_adds",
    "host_adds",
    "snapshots",
    "qcs",
    "compact_qcs",
    "agg_verifies",
    "agg_failures",
    "pairings",
    "native_signs",
    "device_calls",
)


class BlsCounts:
    """The process's BLS counters; ``active`` once a BLS part exists."""

    def __init__(self):
        self._lock = threading.Lock()
        self.active = False
        self.counts = dict.fromkeys(FIELDS, 0)

    def add(self, field: str, n: int = 1) -> None:
        # pairings are counted on the verify service's worker threads too
        with self._lock:
            self.counts[field] += n

    def line(self) -> str:
        return " ".join(f"{k}={v}" for k, v in self.counts.items())


BLS_COUNTS = BlsCounts()

__all__ = ["BLS_COUNTS", "BlsCounts", "FIELDS"]
