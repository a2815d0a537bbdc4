"""The node CLI: keys / run / deploy.

Parity target: reference ``node/src/main.rs:15-148`` — ``keys`` writes a
fresh keypair file, ``run`` boots a node from config files, ``deploy``
spins up a whole local committee in one process (the in-process testbed,
main.rs:102-148). ``-v`` repeats raise verbosity; millisecond timestamps
are always on (the reference gates them behind the `benchmark` feature —
they're the tracing schema here, SURVEY.md §5).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import selectors
import sys
import time

from ..consensus import Committee, Parameters
from .config import (
    ConfigError,
    Secret,
    read_committee,
    write_committee,
    write_parameters,
)
from ..telemetry import spans as _spans
from .node import Node

log = logging.getLogger("node")

LEVELS = [logging.ERROR, logging.WARNING, logging.INFO, logging.DEBUG]


class _IdleSpanSelector(selectors.DefaultSelector):
    """The event loop's selector with its waiting named: a ``select``
    that may block is the ``loop.idle`` span, so in a profiler trace
    the loop thread's busy time is the window less ``loop.idle``."""

    def select(self, timeout=None):
        if timeout is not None and timeout <= 0:
            return super().select(timeout)
        with _spans.span("loop.idle"):
            return super().select(timeout)


class _SpannedEventLoop(asyncio.SelectorEventLoop):
    """The loop ``run`` and ``run-many`` serve on, named in a profiler
    trace: its waiting is ``loop.idle`` (the selector above), and while
    a session is active every handle it runs is one ``cb`` span
    (``spans.trace_callbacks``), so the busy time no layer span covers
    is split into the callbacks that held it and the loop's own
    machinery between them.  Once a pass it asks the profiler's switch
    (the one ``spans.span`` reads) and swaps ``Handle._run`` only when
    the switch has flipped: with no session the stdlib's ``_run`` runs
    every callback."""

    def __init__(self):
        super().__init__(_IdleSpanSelector())
        self._traced = False  # what this loop last gave trace_callbacks

    def _process_events(self, event_list):
        if _spans._tracing() != self._traced:
            self._traced = not self._traced
            _spans.trace_callbacks(self._traced)
        super()._process_events(event_list)

    def close(self):
        if self._traced:
            self._traced = False
            _spans.trace_callbacks(False)
        super().close()


def _new_event_loop() -> asyncio.AbstractEventLoop:
    return _SpannedEventLoop()


async def _with_host_stats(serving) -> None:
    """Await ``serving`` with the process's ``Host stats:`` probe
    (telemetry/hoststats.py) running beside it."""
    from ..telemetry import hoststats

    probe = hoststats.start()
    try:
        await serving
    finally:
        probe.cancel()


class _FastFormatter(logging.Formatter):
    """The harness log-line format with the per-record strftime cached
    per second: at ~10 load-bearing INFO lines per committed block the
    default Formatter's asctime path (strftime + two %-formats) was a
    measurable slice of the one-core round.  Output is byte-identical
    to the basicConfig format below."""

    def __init__(self):
        super().__init__()
        self._last_sec: int | None = None
        self._last_prefix = ""

    def format(self, record: logging.LogRecord) -> str:
        sec = int(record.created)
        if sec != self._last_sec:
            import time as _time

            self._last_sec = sec
            self._last_prefix = _time.strftime(
                "%Y-%m-%dT%H:%M:%S", _time.localtime(sec)
            )
        msg = record.getMessage()
        if record.exc_info and not record.exc_text:
            record.exc_text = self.formatException(record.exc_info)
        if record.exc_text:
            msg = f"{msg}\n{record.exc_text}"
        if record.stack_info:
            msg = f"{msg}\n{self.formatStack(record.stack_info)}"
        return (
            f"{self._last_prefix}.{int(record.msecs):03d}Z "
            f"[{record.levelname}] {record.name} {msg}"
        )


def setup_logging(verbosity: int) -> None:
    import os

    # HOTSTUFF_LOG_LEVEL overrides the -v count (harness runs pin -vv for
    # the log-scrape contract; this lets an operator crank one run to
    # DEBUG without editing the harness)
    env = os.environ.get("HOTSTUFF_LOG_LEVEL", "")
    level = getattr(logging, env.upper(), None) if env else None
    logging.basicConfig(
        level=level if level is not None else LEVELS[min(verbosity, 3)],
        format="%(asctime)s.%(msecs)03dZ [%(levelname)s] %(name)s %(message)s",
        datefmt="%Y-%m-%dT%H:%M:%S",
    )
    for handler in logging.getLogger().handlers:
        handler.setFormatter(_FastFormatter())


def _freeze_boot_objects() -> None:
    """Move boot-time immortals (committee state, caches, and — with a
    device verifier — the whole jax runtime) out of the GC's collected
    generations: steady-state collections otherwise scan megabytes of
    permanent objects every pass, which a one-core rig feels directly in
    round latency (measured ~2x consensus-latency cut at 16 nodes)."""
    import gc
    import os

    gc.collect()
    gc.freeze()
    # Full (gen2) collections re-scan every live object and measured
    # 30-55 ms per pause on this rig — a pause that spans ~10 consensus
    # rounds and is the worst mode in the round-gap histogram.  gen0/1
    # keep the default cadence (young garbage is the bulk and collects
    # in ~0.15 ms); gen2 runs 50x less often, turning a per-20 s stall
    # into a per-~15 min one.  Cyclic garbage surviving gen1 accumulates
    # until then — the stretch is paired with a scheduled off-peak full
    # collection below so the accumulation is bounded by the sweep
    # period, not by the (now rare) threshold trigger.
    # HOTSTUFF_GC_GEN2_STRETCH=0 opts out (default thresholds kept) for
    # workloads whose allocation profile is cycle-heavy.
    stretch = os.environ.get("HOTSTUFF_GC_GEN2_STRETCH", "1").strip().lower()
    if stretch in ("", "0", "false", "no", "off"):
        return
    g0, g1, _ = gc.get_threshold()
    gc.set_threshold(g0, g1, 500)
    period = float(os.environ.get("HOTSTUFF_GC_GEN2_PERIOD", "300") or 300)
    if period <= 0:
        return

    async def _gen2_sweep() -> None:
        import time

        glog = logging.getLogger(__name__)
        while True:
            await asyncio.sleep(period)
            t0 = time.perf_counter()
            freed = gc.collect(2)
            glog.debug(
                "scheduled gen2 sweep: %d collected in %.1f ms",
                freed,
                (time.perf_counter() - t0) * 1e3,
            )

    asyncio.ensure_future(_gen2_sweep())


def _metrics_port(args) -> int | None:
    """The /metrics port: ``--metrics-port`` first, then the
    HOTSTUFF_METRICS_PORT env knob; None = endpoint off (default)."""
    port = getattr(args, "metrics_port", None)
    if port is not None:
        return port
    import os

    env = os.environ.get("HOTSTUFF_METRICS_PORT", "").strip()
    if not env:
        return None
    try:
        return int(env)
    except ValueError:
        log.warning("ignoring non-integer HOTSTUFF_METRICS_PORT=%r", env)
        return None


def _apply_journal_dir(args) -> None:
    """Force-enable the flight recorder when ``--journal-dir`` was given
    (the env knobs HOTSTUFF_JOURNAL / HOTSTUFF_JOURNAL_DIR work without
    the flag; off by default)."""
    jdir = getattr(args, "journal_dir", None)
    if jdir:
        from .. import telemetry

        telemetry.set_journal_dir(jdir)


def _apply_profile(args) -> None:
    """Turn the verify-pipeline span profiler on when ``--profile`` was
    given: sets HOTSTUFF_PROFILE (so worker threads and any child
    processes inherit the switch) and force-enables the recorder (env
    check may already have been consumed by an earlier import)."""
    if getattr(args, "profile", False):
        import os

        from .. import telemetry

        os.environ["HOTSTUFF_PROFILE"] = "1"
        telemetry.spans.enable()


def _apply_verify_pipeline(args) -> None:
    """Bridge ``--verify-pipeline N`` into HOTSTUFF_VERIFY_PIPELINE (the
    env-first pattern every other knob uses) so the async verify
    service — and any child node processes — pick the dispatch pipeline
    depth up at service construction."""
    depth = getattr(args, "verify_pipeline", None)
    if depth is not None:
        import os

        os.environ["HOTSTUFF_VERIFY_PIPELINE"] = str(max(1, depth))


def _apply_mesh_devices(args) -> None:
    """Bridge ``--mesh-devices N`` into HOTSTUFF_MESH_DEVICES (the
    env-first pattern) so the sharded verifier sizes its device mesh at
    materialization — in this process and in any child node process the
    deploy path spawns."""
    n = getattr(args, "mesh_devices", None)
    if n is not None:
        import os

        os.environ["HOTSTUFF_MESH_DEVICES"] = str(max(1, n))


def _apply_ingest(args) -> None:
    """Bridge the ingest-plane knobs into their env-first homes:
    ``--max-pending`` -> HOTSTUFF_MAX_PENDING (proposer buffer cap, the
    admission controller's capacity) and ``--ingest-watermark`` ->
    HOTSTUFF_INGEST_WATERMARK (shed threshold as a fraction of that
    cap).  See docs/LOAD.md."""
    import os

    n = getattr(args, "max_pending", None)
    if n is not None:
        os.environ["HOTSTUFF_MAX_PENDING"] = str(max(1, n))
    w = getattr(args, "ingest_watermark", None)
    if w is not None:
        os.environ["HOTSTUFF_INGEST_WATERMARK"] = str(w)


def _apply_health(args) -> None:
    """Activate the live health plane when ``--health`` was given: sets
    HOTSTUFF_HEALTH (env-first, inherited by child node processes) so
    every booted node runs the per-node HealthMonitor
    (telemetry/health.py) — online detectors, ``health.*`` incident
    journal edges, and the bounded campaign recorder."""
    import os

    if getattr(args, "health", False):
        os.environ["HOTSTUFF_HEALTH"] = "1"


def _apply_fresh_state(args) -> None:
    """Bridge ``--fresh-state`` into HOTSTUFF_FRESH_STATE: an explicit
    escape hatch forcing every booted node to discard its persisted
    store.  Normally unnecessary — the committee-hash provenance check
    (node.py) already rejects state from a different committee, and
    matching state is exactly what crash recovery and snapshot
    state-sync want to keep."""
    import os

    if getattr(args, "fresh_state", False):
        os.environ["HOTSTUFF_FRESH_STATE"] = "1"


def _apply_fault_plane(args) -> None:
    """Activate the chaos plane when ``--fault-plane`` was given: the
    flag value (a spec file path or inline JSON) lands in
    HOTSTUFF_FAULTS, which Consensus.spawn reads at boot — exactly the
    env-first pattern the WAN and journal knobs use."""
    import os

    spec = getattr(args, "fault_plane", None)
    if spec:
        os.environ["HOTSTUFF_FAULTS"] = spec


def _apply_adversary(args) -> None:
    """Activate the Byzantine adversary plane when ``--adversary`` was
    given: the flag value (a spec file path or inline JSON naming the
    attacking node indexes and policy windows) lands in
    HOTSTUFF_ADVERSARY, which Consensus.spawn reads at boot.  Inert on
    nodes the spec does not name, so the whole committee can share one
    spec file."""
    import os

    spec = getattr(args, "adversary", None)
    if spec:
        os.environ["HOTSTUFF_ADVERSARY"] = spec


async def _run_node(args) -> None:
    from .. import telemetry

    # before Node.new: a configured endpoint force-enables collection,
    # and the nodes booted below only pick telemetry up at boot
    _apply_journal_dir(args)
    _apply_fault_plane(args)
    _apply_adversary(args)
    _apply_profile(args)
    _apply_verify_pipeline(args)
    _apply_mesh_devices(args)
    _apply_ingest(args)
    _apply_health(args)
    _apply_fresh_state(args)
    await telemetry.maybe_start_server(_metrics_port(args))
    node = await Node.new(
        committee_file=args.committee,
        key_file=args.keys,
        store_path=args.store,
        parameters_file=args.parameters,
        verifier_backend=args.verifier,
        transport=args.transport,
    )
    _freeze_boot_objects()
    # serve() instead of analyze_block(): a node voted out by a
    # committed reconfiguration exits cleanly after its grace window
    await _with_host_stats(node.serve())


async def _submit_reconfig(args) -> int:
    """Craft, sign, and broadcast a reconfiguration op (docs/RECONFIG.md):
    the NEW epoch's committee (``--new-committee`` file) plus an
    activation margin Δ, sponsored by the member whose key file is
    given.  Every current member receives the op; whichever becomes
    leader first proposes it on-chain."""
    import dataclasses
    import os

    from ..consensus.reconfig import ReconfigOp, newest_epoch
    from ..consensus.wire import encode_reconfig
    from ..crypto import Digest
    from ..crypto.scheme import make_signing_service
    from ..network import SimpleSender

    current = read_committee(args.committee)
    new_com = read_committee(args.new_committee)
    if hasattr(new_com, "entries"):  # a schedule file: its newest epoch
        new_com = new_com.committees()[-1]
    secret = Secret.read(args.keys)
    epoch = (
        args.epoch
        if args.epoch is not None
        else max(new_com.epoch, newest_epoch(current) + 1)
    )
    if epoch != new_com.epoch:
        new_com = dataclasses.replace(new_com, epoch=epoch)
    margin = (
        args.margin
        if args.margin is not None
        else int(os.environ.get("HOTSTUFF_RECONFIG_MARGIN", "8"))
    )
    op = ReconfigOp(new_committee=new_com, margin=margin, sponsor=secret.name)
    service = make_signing_service(secret.scheme, secret.secret)
    op.signature = await service.request_signature(Digest(op.digest()))
    frame = encode_reconfig(op)
    sender = SimpleSender()
    targets = [
        current.address(nm)
        for nm in current.authorities
        if current.address(nm) is not None
    ]
    log.info(
        "Submitting %r (margin %d) to %d current members",
        op,
        margin,
        len(targets),
    )
    for address in targets:
        await sender.send(address, frame)
    # fire-and-forget senders queue frames; give the connections a
    # moment to flush before tearing the process down
    await asyncio.sleep(float(args.linger))
    sender.close()
    return 0


def _raise_fd_limit(target: int) -> None:
    """Best-effort RLIMIT_NOFILE raise to ``target`` (soft AND hard
    when the process may — root on this rig); silently keeps the
    current limit when it is already enough or the raise is denied."""
    try:
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft >= target:
            return
        # Never LOWER the hard cap: RLIM_INFINITY is -1 on Linux, so the
        # obvious max(hard, target) would replace an unlimited cap with
        # ``target`` — and for a non-root process that shrink is
        # irreversible.  Touch the hard cap only when it is finite and
        # actually below the target.
        if hard != resource.RLIM_INFINITY and hard < target:
            new_hard = target
        else:
            new_hard = hard
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE, (target, new_hard))
        except (ValueError, OSError):
            # can't raise the hard cap: take everything the soft cap allows
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    except (ValueError, OSError, ImportError):
        pass


async def _run_many(args) -> None:
    """Several nodes co-located in ONE process from existing config
    files — the reference's in-process testbed shape (main.rs:102-148)
    driven by the harness's key/committee files.  On a host with fewer
    cores than nodes this removes cross-process scheduling from the
    measured path: every actor shares one asyncio loop."""
    import os

    from .. import telemetry

    _apply_journal_dir(args)
    _apply_fault_plane(args)
    _apply_adversary(args)
    _apply_profile(args)
    _apply_verify_pipeline(args)
    _apply_mesh_devices(args)
    _apply_ingest(args)
    _apply_health(args)
    _apply_fresh_state(args)
    await telemetry.maybe_start_server(_metrics_port(args))
    key_files = args.keys.split(",")
    # Co-location hint: the verifier layer coalesces all these nodes'
    # claims into one device dispatch stream, so the device pays off at
    # committee sizes far below the per-node threshold (node.py warmup).
    os.environ["HOTSTUFF_COLOCATED_NODES"] = str(len(key_files))
    # File-descriptor headroom: n co-located nodes keep one persistent
    # connection per (sender, peer) pair and BOTH socket endpoints live
    # in this process, so a committee-wide timeout broadcast opens up to
    # ~2*n^2 sockets at once (n=256: ~131k — the default 20k limit made
    # a single view-change storm cascade into accept() EMFILE failures
    # and a wedged committee).  Best effort: never lowers the limit and
    # stays inside the hard cap / fs.nr_open.
    _raise_fd_limit(2 * len(key_files) * len(key_files) + 20_000)
    # Where the fd limit cannot cover the committee (a capability-
    # restricted host pins the hard cap: 20,000 on the v5e hosts, where
    # setrlimit may not raise it), bound the per-sender connection pools
    # instead: idle-LRU eviction keeps the process near
    # (n * senders * cap) connections at 2 fds each, at the cost of
    # reconnects as leadership rotates.  Parity (unbounded) is kept
    # whenever the fd budget already fits the quadratic worst case.
    import resource

    n = len(key_files)
    soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    if n > 1 and soft < 2 * n * n + 10_000:
        budget_conns = max(1_000, (soft - 4_000) // 2)
        cap = max(4, budget_conns // (4 * n))
        os.environ.setdefault("HOTSTUFF_MAX_PEER_CONNS", str(cap))
        logging.getLogger(__name__).info(
            "fd budget %d < 2*%d^2: bounding per-sender connection "
            "pools at %s",
            soft,
            n,
            os.environ["HOTSTUFF_MAX_PEER_CONNS"],
        )
    began = time.perf_counter()
    _check_committee_keys(args.committee)
    keys_s = time.perf_counter() - began
    warm_before, nodes_s = Node.warm_s, 0.0
    nodes = []
    for i, key_file in enumerate(key_files):
        began = time.perf_counter()
        nodes.append(
            await Node.new(
                committee_file=args.committee,
                key_file=key_file,
                store_path=f"{args.store_prefix}{i}",
                parameters_file=args.parameters,
                verifier_backend=args.verifier,
                transport=args.transport,
                bind_host="127.0.0.1",
            )
        )
        nodes_s += time.perf_counter() - began
    warm_s = Node.warm_s - warm_before
    # NOTE: scraped (chipbench/readers/boot.py)
    logging.getLogger(__name__).info(
        "Boot stats: nodes=%d keys_s=%.3f nodes_s=%.3f warm_s=%.3f",
        n, keys_s, nodes_s - warm_s, warm_s,
    )
    # Each node's round timer started in its own ``Node.new``, and one
    # process boots the nodes one after another: the first node's timer
    # would count the rest of the committee's boot against its first
    # round (seconds of the 5 s timeout at 256 nodes), as separate hosts
    # booting together would not.  The committee serves from here.
    for node in nodes:
        node.consensus.core.timer.reset()
    _freeze_boot_objects()
    await _with_host_stats(asyncio.gather(*(n.serve() for n in nodes)))


def _check_committee_keys(committee_file: str) -> None:
    """Check every member's proof of possession and decode every BLS
    key once for the process, ahead of the nodes: each ``Node.new``
    then finds them in the process's caches (``crypto/bls/service.py``
    ``check_possession``, ``decoded_key``), so the ``Boot stats:`` line
    can tell the keys' seconds from the nodes'.  An ed25519 committee
    has nothing to check here."""
    committee = read_committee(committee_file)
    committee.verify_pops()
    for member in committee.committees():
        if member.scheme == "bls":
            from ..crypto.bls.service import decoded_key

            for pk in member.authorities:
                decoded_key(pk.to_bytes())


async def _deploy_testbed(
    nodes: int,
    base_port: int,
    scheme: str,
    metrics_port: int | None = None,
    journal_dir: str | None = None,
) -> None:
    """In-process local testbed (reference main.rs:102-148): n fresh
    keypairs, committee.json + node_i.json on disk, every node spawned as
    a task in this process, commit channels drained."""
    from .. import telemetry

    if journal_dir:
        telemetry.set_journal_dir(journal_dir)
    await telemetry.maybe_start_server(metrics_port)
    keys = [Secret.new(scheme) for _ in range(nodes)]
    committee = Committee.new(
        [
            (secret.name, 1, ("127.0.0.1", base_port + i))
            for i, secret in enumerate(keys)
        ],
        scheme=scheme,
        pops={s.name: s.pop for s in keys if s.pop is not None},
    )
    write_committee(committee, ".committee.json")
    write_parameters(Parameters(), ".parameters.json")
    for i, secret in enumerate(keys):
        secret.write(f".node_{i}.json")

    # The testbed's keypairs are FRESH every run, so a leftover .db_*
    # from an earlier deployment can never belong to this committee.
    # No blanket wipe here anymore: Node.new's committee-hash provenance
    # check detects the mismatch and discards the stale store by
    # construction (the "fresh testbed recovers to round ~800" class),
    # while state that DOES match the committee survives for crash
    # recovery and snapshot state-sync.  --fresh-state forces a wipe.
    booted = []
    for i in range(nodes):
        node = await Node.new(
            committee_file=".committee.json",
            key_file=f".node_{i}.json",
            store_path=f".db_{i}",
            parameters_file=".parameters.json",
            bind_host="127.0.0.1",
        )
        booted.append(node)
    log.info("Deployed %d-node local testbed on base port %d", nodes, base_port)
    _freeze_boot_objects()
    await _with_host_stats(asyncio.gather(*(n.serve() for n in booted)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="hotstuff-tpu-node",
        description="A TPU-native implementation of 2-chain HotStuff",
    )
    parser.add_argument("-v", "--verbose", action="count", default=0)
    sub = parser.add_subparsers(dest="command", required=True)

    p_keys = sub.add_parser("keys", help="generate a new keypair file")
    p_keys.add_argument("--filename", required=True)
    p_keys.add_argument(
        "--scheme",
        choices=["ed25519", "bls"],
        default="ed25519",
        help="signature scheme (the committee file records the same "
        "scheme; BLS gives constant-cost aggregate QC verification)",
    )

    p_run = sub.add_parser("run", help="run a node")
    p_run.add_argument("--keys", required=True)
    p_run.add_argument("--committee", required=True)
    p_run.add_argument("--store", required=True)
    p_run.add_argument("--parameters", default=None)
    p_run.add_argument(
        "--transport",
        choices=["asyncio", "native"],
        default="asyncio",
        help="framed-TCP transport: asyncio (default) or the native C++ "
        "epoll reactor (network/native.py)",
    )
    p_run.add_argument(
        "--verifier",
        choices=["cpu", "tpu", "tpu-sharded", "mesh"],
        default="cpu",
        help="signature verification backend ('mesh' is the sharded "
        "multi-chip backend, an alias of tpu-sharded)",
    )
    metrics_help = (
        "serve Prometheus /metrics on this port and enable telemetry "
        "(0 = ephemeral port, logged at startup; default: off, or the "
        "HOTSTUFF_METRICS_PORT env knob)"
    )
    p_run.add_argument(
        "--metrics-port", type=int, default=None, help=metrics_help
    )
    journal_help = (
        "enable the consensus flight recorder and write its JSONL ring "
        "segments under this directory (default: off, or the "
        "HOTSTUFF_JOURNAL / HOTSTUFF_JOURNAL_DIR env knobs; merge "
        "journals with `python -m benchmark traces`)"
    )
    p_run.add_argument("--journal-dir", default=None, help=journal_help)
    profile_help = (
        "enable the verify-pipeline span profiler (ring-buffered "
        "per-stage spans, verify_stage_ms metrics, and — with the "
        "flight recorder on — a 'verify pipeline' Perfetto track; "
        "default: off, or the HOTSTUFF_PROFILE env knob)"
    )
    p_run.add_argument("--profile", action="store_true", help=profile_help)
    faults_help = (
        "activate the chaos plane from this fault-spec file (or inline "
        "JSON): seeded deterministic drop/delay/duplicate/corrupt per "
        "directed peer pair on a scenario timeline (docs/FAULTS.md; "
        "default: off, or the HOTSTUFF_FAULTS env knob)"
    )
    p_run.add_argument("--fault-plane", default=None, help=faults_help)
    adversary_help = (
        "activate the Byzantine adversary plane from this spec file (or "
        "inline JSON): seeded deterministic protocol-level attacks — "
        "equivocate, forge-qc, withhold, double-vote, flood, collude — "
        "on the named node indexes (docs/FAULTS.md; default: off, or "
        "the HOTSTUFF_ADVERSARY env knob)"
    )
    p_run.add_argument("--adversary", default=None, help=adversary_help)
    pipeline_help = (
        "verify dispatch pipeline depth: device waves in flight at once "
        "(default: 2, or the HOTSTUFF_VERIFY_PIPELINE env knob; 1 "
        "restores the single-in-flight dispatch gate)"
    )
    p_run.add_argument(
        "--verify-pipeline",
        type=int,
        default=None,
        metavar="N",
        help=pipeline_help,
    )
    mesh_help = (
        "device count for the sharded mesh verifier (default: every "
        "visible device, or the HOTSTUFF_MESH_DEVICES env knob; only "
        "meaningful with --verifier mesh/tpu-sharded)"
    )
    p_run.add_argument(
        "--mesh-devices", type=int, default=None, metavar="N", help=mesh_help
    )
    max_pending_help = (
        "proposer payload buffer cap / ingest admission capacity "
        "(default 100000, or the HOTSTUFF_MAX_PENDING env knob)"
    )
    watermark_help = (
        "buffer-occupancy fraction above which the ingest plane sheds "
        "producer payloads with a typed BUSY reply (default 0.75, or "
        "HOTSTUFF_INGEST_WATERMARK)"
    )
    p_run.add_argument(
        "--max-pending",
        type=int,
        default=None,
        metavar="N",
        help=max_pending_help,
    )
    fresh_state_help = (
        "discard any persisted store before booting (escape hatch; by "
        "default matching state is recovered and mismatched-committee "
        "state is rejected by the provenance check)"
    )
    p_run.add_argument(
        "--ingest-watermark",
        type=float,
        default=None,
        metavar="F",
        help=watermark_help,
    )
    health_help = (
        "enable the live health plane: per-node online anomaly "
        "detectors (leader-stall, view-change storm, commit collapse, "
        "shed storm), health.* incident journal edges, the /delta "
        "streaming-export route, and the bounded campaign recorder "
        "(docs/TELEMETRY.md; default: off, or the HOTSTUFF_HEALTH env "
        "knob)"
    )
    p_run.add_argument("--health", action="store_true", help=health_help)
    p_run.add_argument(
        "--fresh-state", action="store_true", help=fresh_state_help
    )

    p_many = sub.add_parser(
        "run-many",
        help="run several nodes in one process from existing config files",
    )
    p_many.add_argument("--keys", required=True, help="comma-separated key files")
    p_many.add_argument("--committee", required=True)
    p_many.add_argument("--store-prefix", required=True)
    p_many.add_argument("--parameters", default=None)
    p_many.add_argument(
        "--transport", choices=["asyncio", "native"], default="asyncio"
    )
    p_many.add_argument(
        "--verifier",
        choices=["cpu", "tpu", "tpu-sharded", "mesh"],
        default="cpu",
    )
    p_many.add_argument(
        "--metrics-port", type=int, default=None, help=metrics_help
    )
    p_many.add_argument("--journal-dir", default=None, help=journal_help)
    p_many.add_argument("--profile", action="store_true", help=profile_help)
    p_many.add_argument("--fault-plane", default=None, help=faults_help)
    p_many.add_argument("--adversary", default=None, help=adversary_help)
    p_many.add_argument(
        "--verify-pipeline",
        type=int,
        default=None,
        metavar="N",
        help=pipeline_help,
    )
    p_many.add_argument(
        "--mesh-devices", type=int, default=None, metavar="N", help=mesh_help
    )
    p_many.add_argument(
        "--max-pending",
        type=int,
        default=None,
        metavar="N",
        help=max_pending_help,
    )
    p_many.add_argument(
        "--ingest-watermark",
        type=float,
        default=None,
        metavar="F",
        help=watermark_help,
    )
    p_many.add_argument("--health", action="store_true", help=health_help)
    p_many.add_argument(
        "--fresh-state", action="store_true", help=fresh_state_help
    )

    p_rec = sub.add_parser(
        "reconfig",
        help="submit a signed committee reconfiguration to the live "
        "committee (docs/RECONFIG.md)",
    )
    p_rec.add_argument(
        "--keys",
        required=True,
        help="key file of the sponsoring CURRENT member",
    )
    p_rec.add_argument(
        "--committee",
        required=True,
        help="the current committee (or schedule) file — submission "
        "targets and epoch numbering",
    )
    p_rec.add_argument(
        "--new-committee",
        required=True,
        help="committee file holding the NEXT epoch's full membership",
    )
    p_rec.add_argument(
        "--margin",
        type=int,
        default=None,
        metavar="N",
        help="activation margin Δ in rounds after the commit (default "
        "8, or the HOTSTUFF_RECONFIG_MARGIN env knob)",
    )
    p_rec.add_argument(
        "--epoch",
        type=int,
        default=None,
        metavar="N",
        help="override the new committee's epoch number (default: "
        "newest known epoch + 1)",
    )
    p_rec.add_argument(
        "--linger",
        type=float,
        default=1.0,
        metavar="S",
        help="seconds to keep the submission connections open (flush)",
    )

    p_dep = sub.add_parser("deploy", help="deploy a local testbed")
    p_dep.add_argument("--nodes", type=int, required=True)
    p_dep.add_argument("--base-port", type=int, default=25_200)
    p_dep.add_argument(
        "--scheme", choices=["ed25519", "bls"], default="ed25519"
    )
    p_dep.add_argument(
        "--metrics-port", type=int, default=None, help=metrics_help
    )
    p_dep.add_argument("--journal-dir", default=None, help=journal_help)
    p_dep.add_argument("--profile", action="store_true", help=profile_help)
    p_dep.add_argument("--fault-plane", default=None, help=faults_help)
    p_dep.add_argument("--adversary", default=None, help=adversary_help)
    p_dep.add_argument(
        "--verify-pipeline",
        type=int,
        default=None,
        metavar="N",
        help=pipeline_help,
    )
    p_dep.add_argument(
        "--mesh-devices", type=int, default=None, metavar="N", help=mesh_help
    )
    p_dep.add_argument(
        "--max-pending",
        type=int,
        default=None,
        metavar="N",
        help=max_pending_help,
    )
    p_dep.add_argument(
        "--ingest-watermark",
        type=float,
        default=None,
        metavar="F",
        help=watermark_help,
    )
    p_dep.add_argument("--health", action="store_true", help=health_help)
    p_dep.add_argument(
        "--fresh-state", action="store_true", help=fresh_state_help
    )

    args = parser.parse_args(argv)
    setup_logging(args.verbose)

    if args.command == "keys":
        Secret.new(args.scheme).write(args.filename)
        return 0
    if args.command in ("run", "run-many"):
        try:
            # sanity-check the committee file before booting
            read_committee(args.committee)
            asyncio.run(
                _run_node(args) if args.command == "run" else _run_many(args),
                loop_factory=_new_event_loop,
            )
        except ConfigError as e:
            # a configuration this host cannot serve: unreadable files,
            # or a device verifier without its device
            log.error("Cannot boot: %s", e)
            return 1
        return 0
    if args.command == "reconfig":
        return asyncio.run(_submit_reconfig(args))
    if args.command == "deploy":
        _apply_fault_plane(args)
        _apply_adversary(args)
        _apply_profile(args)
        _apply_verify_pipeline(args)
        _apply_mesh_devices(args)
        _apply_ingest(args)
        _apply_health(args)
        _apply_fresh_state(args)
        asyncio.run(
            _deploy_testbed(
                args.nodes,
                args.base_port,
                args.scheme,
                metrics_port=_metrics_port(args),
                journal_dir=getattr(args, "journal_dir", None),
            )
        )
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
