"""The BLS committee's aggregation: the ``BLS stats:`` counter line, the
``Compact QC`` line every leader logs, and the ``bls.*`` and ``agg.*``
spans and the device's G1 programs in the profiler's trace.

``bls64`` is ``colo64`` with the scheme changed, and what the change
costs lies here:

- **Signing** (``bls.sign``: hash to G1 and a scalar multiply in pure
  Python, one a vote and a block, on the event loop) and **the running
  sum** (``bls.decode``: a vote signature decompressed; ``agg.accumulate``:
  its add dispatched to the device; ``agg.snapshot``: the fence and the
  read back at quorum) are self time on the loop thread, all nodes,
  over the rounds begun in the traced window, as
  ``chipbench/hostspans.py`` counts a layer's.  ``hostspans.trace_events``
  keeps only the layer spans it knows, so this file reads the trace
  itself, with ``chipbench/loopcalls.py``'s rule for the loop thread.
- **The device's add**: the operations of the ``XLA Ops`` line that lie
  inside an execution of the running-sum program on the ``XLA Modules``
  line (``jit__running_add_impl``), summed an execution and averaged.
- **The counters** (``hotstuff_tpu/telemetry/blsstats.py``): cumulative,
  one line a process every 5 s; the window's share is the last line at
  or before its end less the last at or before its start, as for
  ``Host stats`` (``hoststats.window_delta``), and its rounds are the
  blocks made between those two lines' stamps.
- **The reference**: every ``Compact QC`` line in the window carries its
  round, signer bitmap, aggregate and the signers' vote signatures; the
  aggregate must equal, byte for byte, what
  ``chipbench/reference/bls_g1_ref.py`` (a copy of the program's
  ``crypto/bls_g1_ref.py``, so that the benchmark does not judge the
  program with the program's code) computes from those signatures, and
  the bitmap must name as many signers as there are signatures.

An untraced run gives None for the span and device metrics; a program
that prints no such lines (a parent commit, an ed25519 committee) gives
None for every metric here, and the line leaves them out.

Run as a program (``python chipbench/readers/bls.py <trace dir>``, with
``JAX_PLATFORMS=cpu`` once the chip's holder has gone) it writes the
events as JSON::

    {"loop": [[name, start_ns, duration_ns, {id: value}], ...],
     "modules": [[name, start_ns, duration_ns], ...],
     "ops": [[name, start_ns, duration_ns], ...]}
"""

from __future__ import annotations

import bisect
import calendar
import glob
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from chipbench.hostspans import OPS_LINE, layer_of, self_times  # noqa: E402
from chipbench.logs import RE_LINE  # noqa: E402
from chipbench.loopcalls import event_of, loop_thread  # noqa: E402
from chipbench.readers.hostspans import run_dir_of  # noqa: E402
from chipbench.readers.hoststats import window_delta  # noqa: E402
from chipbench.reference import bls_g1_ref  # noqa: E402

#: the loop's BLS spans (``hotstuff_tpu/telemetry/taxonomy.py``)
SIGN = ("bls.sign",)
DEVICE_SUM = ("bls.decode", "agg.accumulate", "agg.snapshot")
#: the device plane's line of program executions
MODULES_LINE = "XLA Modules"
#: the running-sum program's name there (``tpu/bls.py``)
RUNNING_ADD = "_running_add_impl"
STATS = "BLS stats: "
RE_QC = re.compile(r"Compact QC round (\d+) signers (\w+) agg (\w+) sigs (\S*)")


# ---- the trace, read in a process of its own -------------------------------


def kept(name: str) -> bool:
    return name in SIGN + DEVICE_SUM or layer_of(name) is not None


def trace_events(trace_dir: str) -> dict:
    """The loop thread's BLS spans and its rounds' spans, and the first
    device's program executions and operations, from the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    )
    out = {"loop": [], "modules": [], "ops": []}
    if not paths:
        return out
    lines, device = [], None
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            lines += [
                [event_of(e.name, e.start_ns, e.duration_ns, e.stats)
                 for e in line.events if kept(e.name)]
                for line in plane.lines
            ]
        elif plane.name.startswith("/device:TPU:") and device is None:
            device = plane
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key:
                    out[key] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
    out["loop"] = [
        e for e in loop_thread(lines)
        if e[0] in SIGN + DEVICE_SUM + ("proposer.make", "core.proposal")
    ]
    return out


def reduce(events: dict) -> dict | None:
    """Self time a round of the BLS spans on the loop thread, and the
    running-sum program's device time an execution; None without a BLS
    span or a round."""
    loop = events.get("loop") or []
    if not any(e[0] in SIGN + DEVICE_SUM for e in loop):
        return None
    rounds = {
        e[3]["round"] for e in loop
        if e[0] == "proposer.make" and "round" in e[3]
    } or {
        e[3]["round"] for e in loop
        if e[0] == "core.proposal" and "round" in e[3]
    }
    if not rounds:
        return None
    own: dict[str, list[int]] = {}
    for event, ns in self_times(loop):
        entry = own.setdefault(event[0], [0, 0])
        entry[0] += ns
        entry[1] += 1
    per_round = lambda names: sum(  # noqa: E731
        own.get(n, [0])[0] for n in names
    ) / 1e6 / len(rounds)
    adds = []
    ops = sorted(events.get("ops") or [], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    for name, start, duration in events.get("modules") or []:
        if RUNNING_ADD not in name:
            continue
        end = start + duration
        i = bisect.bisect_left(starts, start)
        inside = 0
        while i < len(ops) and ops[i][1] < end:
            inside += min(ops[i][1] + ops[i][2], end) - ops[i][1]
            i += 1
        adds.append(inside)
    return {
        "rounds": len(rounds),
        "sign_ms_per_round": per_round(SIGN),
        "device_sum_ms_per_round": per_round(DEVICE_SUM),
        "span_self_ms_per_round": {
            name: [ns / 1e6 / len(rounds), count]
            for name, (ns, count) in sorted(own.items())
        },
        "running_adds": len(adds),
        "running_add_us": sum(adds) / len(adds) / 1e3 if adds else None,
    }


def _traced(run) -> dict | None:
    """The reduction of this run's trace, read once; it is written to
    ``bls_breakdown.json`` beside ``detail.json``."""
    if hasattr(run, "_bls_trace"):
        return run._bls_trace
    run._bls_trace = None
    run_dir = run_dir_of(run)
    if run_dir is None or not os.path.isdir(os.path.join(run_dir, "trace")):
        return None
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             os.path.join(run_dir, "trace")],
            capture_output=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=300,
        )
        if out.returncode != 0:
            sys.stderr.write(out.stderr.decode("utf-8", "replace")[-2000:])
            return None
        run._bls_trace = reduce(json.loads(out.stdout))
        if run._bls_trace is not None:
            with open(os.path.join(run_dir, "bls_breakdown.json"), "w") as f:
                json.dump(run._bls_trace, f, indent=1)
    except (OSError, ValueError, subprocess.SubprocessError) as e:
        sys.stderr.write(f"chipbench: BLS spans not read: {e}\n")
    return run._bls_trace


# ---- the committee's log ----------------------------------------------------


def log_lines(text: str) -> tuple[list, list]:
    """``(stamp, counters)`` of every ``BLS stats`` line and ``(stamp,
    round, bitmap, aggregate, signatures)`` of every ``Compact QC``
    line, hex as the program prints it."""
    stats, qcs = [], []
    for line in text.splitlines():
        at = line.find(STATS)
        is_qc = "Compact QC round " in line
        m = RE_LINE.match(line) if at >= 0 or is_qc else None
        if m is None:
            continue
        second = time.strptime(m.group(1), "%Y-%m-%dT%H:%M:%S")
        stamp = calendar.timegm(second) + int(m.group(2)) / 1000.0
        if at >= 0:
            try:
                stats.append((stamp, {
                    k: float(v) for k, v in (
                        item.split("=")
                        for item in line[at + len(STATS):].split()
                    )
                }))
            except ValueError:
                continue
        else:
            qc = RE_QC.search(m.group(4))
            if qc is not None:
                rnd, bitmap, agg, sigs = qc.groups()
                qcs.append((stamp, int(rnd), bitmap, agg,
                            sigs.split(",") if sigs else []))
    return stats, qcs


def _log(run) -> tuple[list, list]:
    if not hasattr(run, "_bls_log"):
        run._bls_log = ([], [])
        run_dir = run_dir_of(run)
        if run_dir is not None:
            try:
                with open(os.path.join(run_dir, "node.log"), "rb") as f:
                    run._bls_log = log_lines(f.read().decode("utf-8", "replace"))
            except OSError:
                pass
    return run._bls_log


def _window(run) -> tuple[dict, int] | None:
    """The counters' window share and the blocks made between the two
    lines it is taken from."""
    stats = _log(run)[0]
    d = window_delta(stats, run.t0, run.t1)
    if d is None:
        return None
    upto = [s for s, _ in stats if s <= run.t1]
    s1 = upto[-1]
    s0 = s1 - d["wall_s"]
    blocks = sum(s0 < made <= s1 for made, _n, _r, _ids in run.log.created.values())
    return d, blocks


def agree(bitmap: str, agg: str, sigs: list[str]) -> bool:
    """Whether a logged compact QC's aggregate is the reference's sum of
    its vote signatures, and its bitmap names as many signers."""
    try:
        return (
            bin(int(bitmap, 16)).count("1") == len(sigs)
            and bls_g1_ref.sum_compressed(bytes.fromhex(s) for s in sigs)
            == bytes.fromhex(agg)
        )
    except ValueError:
        return False


# ---- the metrics -------------------------------------------------------------


def sign_ms_per_round(run):
    r = _traced(run)
    return r["sign_ms_per_round"] if r else None


def device_sum_ms_per_round(run):
    """What the device sum costs the loop: ``bls.decode``,
    ``agg.accumulate`` and ``agg.snapshot``."""
    r = _traced(run)
    return r["device_sum_ms_per_round"] if r else None


def g1_add_us(run):
    r = _traced(run)
    return r["running_add_us"] if r else None


def pairings_per_round(run):
    w = _window(run)
    if w is None or not w[1]:
        return None
    return w[0]["pairings"] / w[1]


def compact_qc_share(run):
    """QCs made in the window in the compact form, over all QCs made; a
    compact certificate that failed verification counts as one that was
    not made compact."""
    w = _window(run)
    if w is None or not w[0]["qcs"]:
        return None
    d = w[0]
    return 100.0 * max(0.0, d["compact_qcs"] - d["agg_failures"]) / d["qcs"]


def reference_agree_share(run):
    """Compact QCs logged in the window whose aggregate the reference
    computes from their signers' vote signatures, over those logged."""
    inside = [q for q in _log(run)[1] if run.t0 <= q[0] < run.t1]
    if not inside:
        return None
    return 100.0 * sum(agree(*q[2:]) for q in inside) / len(inside)


if __name__ == "__main__":
    from chipbench import ending

    # ``run.py`` waits for this reader; killed meanwhile, it is missed
    ending.die_with_parent()
    json.dump(trace_events(sys.argv[1]), sys.stdout)
