"""Harness task entry points (the reference's fabfile, without Fabric).

    python -m benchmark local   --nodes 4 --rate 1000 --duration 20
    python -m benchmark tpu     --sizes 4,8,16 --rate 1000
    python -m benchmark aggregate
    python -m benchmark plot

``local``  — one run, SUMMARY to stdout and results/.
``tpu``    — committee-size sweep co-located on this machine with the TPU
             verifier backend, one process per committee (the
             BASELINE.json `fab tpu` task).
``aggregate`` / ``plot`` — summarize / chart the results directory.
"""

from __future__ import annotations

import argparse
import sys

from .aggregate import aggregate, print_summary
from .local import LocalBench
from .utils import BenchError, PathMaker, Print, save_result as _save_result


def _run_failed(bench: LocalBench, parser) -> bool:
    """True (and says why) when a committee run proved nothing: a
    process was dead when the window closed, or nothing was committed."""
    for cmd, code in bench.died:
        Print.error(f"process died during the run (exit {code}): {cmd}")
    empty = not parser.has_window()
    if empty:
        Print.error("nothing was committed: no measurement window")
    return bool(bench.died) or empty


def task_local(args) -> int:
    bench = LocalBench(
        nodes=args.nodes,
        rate=args.rate,
        duration=args.duration,
        faults=args.faults,
        timeout_delay=args.timeout_delay,
        verifier=args.verifier,
        transport=args.transport,
        scheme=args.scheme,
        in_process=args.in_process,
        tx_size=args.tx_size,
        wan=args.wan,
        payload_homes=args.payload_homes,
        no_claim_dedup=args.no_claim_dedup,
        journal=args.journal,
        profile=args.profile,
        health=args.health,
    )
    parser = bench.run()
    trace_txt = ""
    if args.journal:
        from .traces import TraceSet

        traces = TraceSet.load(PathMaker.journals_path())
        trace_txt = traces.summary()
        crit_report = None
        if traces.blocks:
            from hotstuff_tpu.telemetry import critpath as crit_engine

            crit_report = crit_engine.analyze(traces)
            if crit_report.commits:
                trace_txt += crit_engine.render(crit_report)
            out = traces.export_chrome_trace(
                PathMaker.trace_file(), critpath=crit_report
            )
            Print.info(
                f"Chrome trace written to {out} "
                "(open in https://ui.perfetto.dev)"
            )
        else:
            Print.warn("journaling was on but no journal records were found")
        if args.health:
            from .traces import merge_campaigns

            campaign = merge_campaigns(
                PathMaker.journals_path(), PathMaker.campaign_file()
            )
            if campaign is not None:
                Print.info(f"Campaign report written to {campaign}")
    label = (
        args.verifier if args.scheme == "ed25519" else f"bls-{args.verifier}"
    )
    if args.no_claim_dedup:
        label += "-nodedup"
    if args.payload_homes != 1:
        label += f"-homes{args.payload_homes}"
    if args.transport != "asyncio":
        label += f"-{args.transport}"
    if args.in_process:
        label += "-1proc"
    if args.wan:
        label += "-wan"
    summary = parser.result(
        faults=args.faults, nodes=args.nodes, verifier=label, extra=trace_txt
    )
    print(summary)
    _save_result(summary, args.faults, args.nodes, args.rate, label,
                 ok=parser.has_window())
    return 1 if _run_failed(bench, parser) else 0


def task_load(args) -> int:
    """Saturation sweep through the admission-controlled ingest plane
    (benchmark/loadgen.py, docs/LOAD.md): walk the offered rate up
    until goodput plateaus, then drive 2x saturation against a small
    proposer buffer and check the backpressure invariant (sheds
    observed, zero silent drop-newest).  Prints the ``+ LOAD`` SUMMARY
    block plus one machine-readable JSON line; exit code 1 when the
    overload run recorded silent drops."""
    import json

    from .loadgen import format_load_block, run_sweep

    result = run_sweep(
        nodes=args.nodes,
        start_rate=args.start_rate,
        duration=args.duration,
        max_steps=args.max_steps,
        clients=args.clients,
        conns_per_node=args.conns,
        tx_size=args.tx_size,
        seed=args.seed,
        overload_max_pending=args.overload_max_pending,
        read_fraction=args.read_fraction,
    )
    block = (
        "\n"
        "-----------------------------------------\n"
        " SUMMARY:\n"
        "-----------------------------------------\n"
        + format_load_block(result)
        + "-----------------------------------------\n"
    )
    print(block)
    _save_result(
        block,
        0,
        args.nodes,
        result["saturation_tx_s"],
        "load",
        ok=result["goodput_tx_s"] > 0,
    )
    # last line: the machine-readable document (scripts/load_check.py)
    print(json.dumps({"load": result}, default=str))
    if result["overload"]["drop_newest"]:
        Print.error("overload run recorded SILENT proposer drops")
        return 1
    return 0


def task_chaos(args) -> int:
    """One committee run under a seeded fault scenario, with the
    committee-wide safety/liveness invariant verdict appended to the
    SUMMARY as a CHAOS block.  Exit code 1 when an invariant fails."""
    import json

    from .chaos import ChaosBench

    spec = None
    if args.spec:
        with open(args.spec) as f:
            spec = json.load(f)
    bench = ChaosBench(
        scenario=args.scenario,
        seed=args.seed,
        nodes=args.nodes,
        rate=args.rate,
        duration=args.duration,
        timeout_delay=args.timeout_delay,
        verifier=args.verifier,
        transport=args.transport,
        journal=args.journal,
        health=args.health,
        spec=spec,
    )
    parser = bench.run()
    ok, chaos_txt = bench.check_invariants()
    trace_txt = ""
    if args.journal:
        from .traces import TraceSet

        traces = TraceSet.load(PathMaker.journals_path())
        trace_txt = traces.summary()
        if traces.blocks:
            out = traces.export_chrome_trace(PathMaker.trace_file())
            Print.info(
                f"Chrome trace written to {out} "
                "(open in https://ui.perfetto.dev)"
            )
        if args.health:
            from .traces import merge_campaigns

            campaign = merge_campaigns(
                PathMaker.journals_path(), PathMaker.campaign_file()
            )
            if campaign is not None:
                Print.info(f"Campaign report written to {campaign}")
    label = f"chaos-{bench.spec.get('name', args.scenario)}"
    if args.transport != "asyncio":
        label += f"-{args.transport}"
    summary = parser.result(
        faults=0, nodes=args.nodes, verifier=label,
        extra=trace_txt + chaos_txt,
    )
    print(summary)
    _save_result(summary, 0, args.nodes, args.rate, label,
                 ok=parser.has_window())
    if not ok:
        Print.error("chaos invariants FAILED")
    return 0 if ok else 1


def _explore_guided(args, out_dir: str) -> int:
    """``explore --guided``: fitness-guided schedule search (ISSUE 18).
    Same run budget as the flat sweep (--seeds); prints a GUIDED
    SUMMARY plus a machine-readable last line for
    scripts/adapt_check.py."""
    import json
    import time

    from hotstuff_tpu.sim import explore_guided

    t0 = time.monotonic()
    result = explore_guided(
        budget=args.seeds,
        nodes=args.nodes,
        start_seed=args.start,
        duration_s=args.duration,
        out_dir=out_dir,
        do_shrink=not args.no_shrink,
        corpus_path=args.corpus,
        scenarios_dir=args.scenarios_dir,
        progress=Print.info,
    )
    dt = time.monotonic() - t0
    print(
        "\n"
        "-----------------------------------------\n"
        " GUIDED EXPLORE SUMMARY:\n"
        "-----------------------------------------\n"
        f" Budget: {result.budget} schedules "
        f"({result.generations} generations, {args.nodes} nodes)\n"
        f" Passed: {result.passed}/{result.budget}\n"
        f" Invariant-threatening: {result.threats} "
        f"(best fitness {result.best_fitness})\n"
        f" Findings: {len(result.findings)}\n"
        f" Promoted: {len(result.promoted)} corpus entries, "
        f"{len(result.scenarios)} canned scenarios\n"
        f" Wall-clock: {dt:.1f}s "
        f"({dt / max(result.budget, 1):.2f}s/schedule)\n"
        "-----------------------------------------"
    )
    for f in result.findings:
        Print.error(
            f"seed {f.seed} ({f.profile}) FAILED: "
            + "; ".join(f.failures[:3])
        )
        if f.repro_dir:
            Print.error(f"  repro bundle: {f.repro_dir}")
    for path in result.scenarios:
        Print.info(f"canned scenario: {path}")
    if result.ok:
        Print.info(
            "every discovered threat was a correctly-contained attack"
        )
    else:
        Print.error("guided search found profile-expectation failures")
    # last line: the machine-readable document (scripts/adapt_check.py)
    print(json.dumps({
        "guided": {
            "budget": result.budget,
            "generations": result.generations,
            "passed": result.passed,
            "threats": result.threats,
            "best_fitness": result.best_fitness,
            "findings": len(result.findings),
            "promoted": [
                {
                    "seed": e["seed"],
                    "profile": e["profile"],
                    "ok": e["ok"],
                    "threats": e["threats"],
                    "journal_digest": e["journal_digest"],
                }
                for e in result.promoted
            ],
            "scenarios": result.scenarios,
            "regimes": result.regimes,
        }
    }))
    return 0 if result.ok else 1


def task_explore(args) -> int:
    """Seeded schedule exploration in the deterministic simulator
    (docs/SIM.md): each seed draws a fault/crash/reconfig schedule, runs
    the whole committee in one process on a virtual-time loop, and
    judges it with the production invariant stack.  Failures get a repro
    bundle plus a greedily-shrunk minimal schedule.  Exit code 1 when
    any seed fails its profile's expectation."""
    import os
    import time

    from hotstuff_tpu.sim import explore

    out_dir = args.out or os.path.join(
        PathMaker.logs_path(), "sim-explore"
    )
    if getattr(args, "guided", False):
        return _explore_guided(args, out_dir)
    t0 = time.monotonic()
    result = explore(
        seeds=args.seeds,
        nodes=args.nodes,
        start_seed=args.start,
        duration_s=args.duration,
        out_dir=out_dir,
        do_shrink=not args.no_shrink,
        progress=Print.info,
    )
    dt = time.monotonic() - t0
    print(
        "\n"
        "-----------------------------------------\n"
        " EXPLORE SUMMARY:\n"
        "-----------------------------------------\n"
        f" Seeds: {result.seeds} (start {args.start}, {args.nodes} nodes)\n"
        f" Passed: {result.passed}/{result.seeds} "
        f"(honest={result.honest} byz={result.byz})\n"
        f" Invariant-threatening: {result.threats}\n"
        f" Findings: {len(result.findings)}\n"
        f" Wall-clock: {dt:.1f}s "
        f"({dt / max(result.seeds, 1):.2f}s/seed)\n"
        "-----------------------------------------"
    )
    for f in result.findings:
        Print.error(
            f"seed {f.seed} ({f.profile}) FAILED: "
            + "; ".join(f.failures[:3])
        )
        if f.repro_dir:
            Print.error(f"  repro bundle: {f.repro_dir}")
        if f.minimal_events is not None:
            kinds = ",".join(ev["kind"] for ev in f.minimal_events)
            Print.error(
                f"  minimal schedule: {len(f.minimal_events)} "
                f"event(s) [{kinds}] — replay with "
                f"`python -m benchmark explore --seeds 1 "
                f"--start {f.seed} --nodes {args.nodes}`"
            )
    if result.ok:
        Print.info("all schedules matched their profile expectations")
    else:
        Print.error("schedule exploration found failures")
    return 0 if result.ok else 1


def task_traces(args) -> int:
    """Merge flight-recorder journals into the cross-node SUMMARY block
    and a Chrome trace-event JSON (open in https://ui.perfetto.dev)."""
    from .traces import TraceSet

    from .traces import merge_campaigns

    traces = TraceSet.load(args.dir)
    campaign = merge_campaigns(args.dir, PathMaker.campaign_file())
    if not traces.journals and campaign is None:
        Print.error(f"no journal segments found under {args.dir}")
        return 1
    if traces.journals:
        from hotstuff_tpu.telemetry import critpath as crit_engine

        report = crit_engine.analyze(traces)
        txt = traces.summary()
        if report.commits:
            txt += crit_engine.render(report)
        print(txt)
        out = traces.export_chrome_trace(args.out, critpath=report)
        Print.info(f"Chrome trace written to {out}")
    if campaign is not None:
        Print.info(f"Campaign report written to {campaign}")
    return 0


def task_critpath(args) -> int:
    """Commit critical-path attribution (telemetry/critpath.py): the
    "+ CRITPATH" SUMMARY block, the Perfetto critical-path track, the
    machine-readable attribution document, and the attribution-diff
    regression gate (``--diff``)."""
    from .critpath import run_critpath

    return run_critpath(
        args.dir,
        out=args.out,
        diff_path=args.diff,
        json_line=args.json,
    )


def task_profile(args) -> int:
    """Span-level verify-pipeline waterfall (benchmark/profile.py):
    QC-shaped claim waves through the production dispatch path with the
    profiler on, per-stage p50/p99 + %-of-e2e SUMMARY per batch size.
    ``--train N`` switches to the sustained wave-train mode instead:
    N distinct-digest waves back to back through the dispatch pipeline,
    amortized per-wave latency and overlap efficiency at depth 1 vs the
    configured pipeline depth."""
    if args.train:
        from .profile import format_train, run_train

        result = run_train(
            size=max(int(s) for s in args.sizes.split(",")),
            train=args.train,
            verifier=args.verifier,
        )
        print(format_train(result))
        return 0

    from .profile import format_waterfall, run_profile

    result = run_profile(
        sizes=tuple(int(s) for s in args.sizes.split(",")),
        waves=args.waves,
        verifier=args.verifier,
        route=args.route,
        capture_dir=args.capture,
    )
    print(format_waterfall(result))
    worst = min(
        (res["coverage_pct"] for res in result["sizes"].values()),
        default=0.0,
    )
    if worst < 90.0:
        Print.warn(
            f"waterfall coverage {worst:.1f}% < 90% — a pipeline stage "
            "is missing instrumentation for this route"
        )
    return 0


def task_tpu(args) -> int:
    """Committee sweep with the TPU crypto backend, co-located on this
    host (one TPU VM), each committee in ONE process: the chip belongs
    to one process at a time."""
    sizes = [int(s) for s in args.sizes.split(",")]
    label = "tpu-1proc"
    failed = False
    for nodes in sizes:
        bench = LocalBench(
            nodes=nodes,
            rate=args.rate,
            duration=args.duration,
            faults=args.faults,
            timeout_delay=args.timeout_delay,
            verifier="tpu",
            in_process=True,
        )
        parser = bench.run()
        summary = parser.result(
            faults=args.faults, nodes=nodes, verifier=label
        )
        print(summary)
        _save_result(summary, args.faults, nodes, args.rate, label,
                     ok=parser.has_window())
        failed |= _run_failed(bench, parser)
    return 1 if failed else 0


def task_remote_lifecycle(args) -> int:
    from .instance import TpuVmManager
    from .remote import RemoteBench
    from .settings import Settings

    settings = Settings.load(args.settings)
    mgr = TpuVmManager(settings)
    if args.lifecycle == "create":
        mgr.create_instances()
    elif args.lifecycle == "destroy":
        mgr.terminate_instances()
    elif args.lifecycle == "start":
        mgr.start_instances()
    elif args.lifecycle == "stop":
        mgr.stop_instances()
    elif args.lifecycle == "info":
        mgr.print_info()
    elif args.lifecycle == "install":
        RemoteBench(settings).install()
    elif args.lifecycle == "update":
        RemoteBench(settings).update()
    elif args.lifecycle == "remote-kill":
        RemoteBench(settings).kill()
    return 0


def task_remote_bench(args) -> int:
    from .remote import RemoteBench
    from .settings import Settings

    bench = RemoteBench(Settings.load(args.settings))
    bench.run(
        nodes_list=[int(s) for s in args.sizes.split(",")],
        rate_list=[int(s) for s in args.rates.split(",")],
        duration=args.duration,
        runs=args.runs,
        faults=args.faults,
        verifier=args.verifier,
        journal=args.journal,
        profile=args.profile,
        fault_plane=args.fault_plane,
        fault_seed=args.fault_seed,
        watch=args.watch,
    )
    return 0


def task_scaling(args) -> int:
    """Committee-scaling decomposition: protocol cost vs host
    starvation (benchmark/scaling.py; VERDICT r2 weak #4)."""
    from .scaling import main as scaling_main

    return scaling_main(
        sizes=[int(s) for s in args.sizes.split(",")],
        rate=args.rate,
        duration=args.duration,
        verifier=args.verifier,
    )


def task_storm(args) -> int:
    """View-change-storm micro-bench (BASELINE config 4): timeout flood,
    TC verify, and committee-scale QC verify per backend."""
    import os

    from .storm import format_report, run_storm

    results = run_storm(
        nodes=args.nodes, device=args.device, bls=not args.no_bls
    )
    report = format_report(args.nodes, results)
    print(report)
    os.makedirs(PathMaker.results_path(), exist_ok=True)
    backends = "-".join(results)
    path = os.path.join(
        PathMaker.results_path(), f"storm-{args.nodes}-{backends}.txt"
    )
    with open(path, "a") as f:
        f.write(report + "\n")
    Print.info(f"Result appended to {path}")
    return 0


def task_logs(args) -> int:
    """Re-parse an existing logs directory and print the SUMMARY
    (reference fabfile.py `logs` task)."""
    from .logs import LogParser

    parser = LogParser.process(args.dir)
    # faults/verifier are not recoverable from logs — print '?' rather
    # than plausible-looking defaults; node count = number of node logs
    print(parser.result(faults="?", nodes=parser.num_node_logs, verifier="?"))
    return 0


def task_watch(args) -> int:
    """Live fleet health dashboard against an already-running committee
    started with --health (docs/TELEMETRY.md)."""
    from .watch import task_watch as _watch

    _watch(args)
    return 0


def task_aggregate(_args) -> int:
    print_summary(aggregate())
    return 0


def task_plot(_args) -> int:
    from .plot import (
        plot_latency_vs_throughput,
        plot_robustness,
        plot_tps_vs_committee,
    )

    groups = aggregate()  # parse the results dir once for all plots
    # WAN-emulated series get their own figure: 300-900 ms WAN latencies
    # on the same linear axis as ~10 ms LAN points would compress the
    # LAN curves to an unreadable band and silently compare
    # incomparable network conditions
    wan_groups = {k: v for k, v in groups.items() if k[3].endswith("-wan")}
    lan_groups = {k: v for k, v in groups.items() if not k[3].endswith("-wan")}
    Print.info(f"Wrote {plot_latency_vs_throughput(lan_groups)}")
    Print.info(f"Wrote {plot_tps_vs_committee(lan_groups)}")
    Print.info(f"Wrote {plot_robustness(lan_groups)}")
    if wan_groups:
        # the reference's published WAN points overlaid (log-x; the
        # hardware gap stays visible)
        Print.info(
            f"Wrote {plot_latency_vs_throughput(wan_groups, reference_overlay=True)}"
        )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark")
    sub = parser.add_subparsers(dest="task", required=True)

    p = sub.add_parser("local")
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--rate", type=int, default=1_000)
    p.add_argument(
        "--tx-size",
        type=int,
        default=512,
        help="payload body bytes (0 = digest-only; 512 = reference parity)",
    )
    p.add_argument("--duration", type=float, default=20.0)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--timeout-delay", type=int, default=5_000)
    p.add_argument("--verifier", choices=["cpu", "tpu", "tpu-sharded", "mesh"], default="cpu")
    p.add_argument(
        "--payload-homes",
        type=int,
        default=1,
        help="nodes receiving each payload (client --homes): 1 = "
        "disjoint queues; more trades duplicate-proposal slack for "
        "earlier proposal (lower e2e latency at large committees)",
    )
    p.add_argument(
        "--wan",
        action="store_true",
        help="emulate the reference's 5-region WAN link delays "
        "(network/wan.py)",
    )
    p.add_argument("--transport", choices=["asyncio", "native"], default="asyncio")
    p.add_argument(
        "--scheme",
        choices=["ed25519", "bls"],
        default="ed25519",
        help="committee signature scheme (bls = aggregate QC verification)",
    )
    p.add_argument(
        "--in-process",
        action="store_true",
        help="co-locate the whole committee in one node process "
        "(run-many; removes OS scheduling noise on few-core hosts; "
        "required with a device verifier, since a chip belongs to one "
        "process)",
    )
    p.add_argument(
        "--journal",
        action="store_true",
        help="enable the consensus flight recorder in every node and "
        "append the cross-node trace reconstruction to the SUMMARY "
        "(journals under logs/journals/, Chrome trace in logs/trace.json)",
    )
    p.add_argument(
        "--profile",
        action="store_true",
        help="verify-pipeline span profiler on in every node "
        "(HOTSTUFF_PROFILE); combine with --journal to get the "
        "'verify pipeline' track in logs/trace.json",
    )
    p.add_argument(
        "--health",
        action="store_true",
        help="health plane on: every node runs the in-process anomaly "
        "monitor + campaign recorder (HOTSTUFF_HEALTH) and serves "
        "/metrics + /delta on port+3000 — attach a live dashboard with "
        "`python -m benchmark watch` (docs/TELEMETRY.md)",
    )
    p.add_argument(
        "--no-claim-dedup",
        action="store_true",
        help="cross-node claim dedup off (HOTSTUFF_NO_CLAIM_DEDUP=1): an "
        "--in-process committee keeps its one shared dispatch stream, and "
        "every node's own copy of every certificate takes lanes of its own "
        "(no verdict crosses a node boundary); with one node a process "
        "nothing is shared and nothing changes",
    )
    p.set_defaults(fn=task_local)

    p = sub.add_parser(
        "load",
        help="saturation sweep through the admission-controlled ingest "
        "plane: open-loop Poisson client fleet, credit-honoring, "
        "goodput-plateau detection + 2x-overload backpressure check "
        "(docs/LOAD.md)",
    )
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument(
        "--start-rate",
        type=int,
        default=500,
        help="first offered rate of the sweep (doubles per step)",
    )
    p.add_argument("--duration", type=float, default=10.0,
                   help="seconds per sweep step")
    p.add_argument("--max-steps", type=int, default=6)
    p.add_argument("--clients", type=int, default=64,
                   help="virtual clients modeled by the fleet")
    p.add_argument("--conns", type=int, default=2,
                   help="connections per node")
    p.add_argument("--tx-size", type=int, default=512)
    p.add_argument("--seed", type=int, default=1,
                   help="Poisson arrival-process seed")
    p.add_argument(
        "--overload-max-pending",
        type=int,
        default=2_000,
        help="proposer buffer cap for the 2x-overload run (small so a "
        "short window can actually reach the shed watermark)",
    )
    p.add_argument(
        "--read-fraction",
        type=float,
        default=0.0,
        help="mixed fleet: probability each arrival is a QC-anchored "
        "ledger read against the replicated execution layer instead "
        "of a write (docs/STATE.md)",
    )
    p.set_defaults(fn=task_load)

    p = sub.add_parser(
        "chaos",
        help="run a committee under a seeded fault scenario and check "
        "the safety/liveness invariants (docs/FAULTS.md)",
    )
    p.add_argument(
        "--scenario",
        default="split-brain",
        help="canned scenario name (hotstuff_tpu/faults/scenarios.py): "
        "split-brain, leader-isolation, flapping-link, "
        "rolling-crash-restart, byz-equivocate, byz-forge-qc, "
        "byz-withhold, byz-collude",
    )
    p.add_argument(
        "--spec",
        default=None,
        help="path to a custom fault-plane spec JSON (overrides "
        "--scenario/--seed)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--rate", type=int, default=1_000)
    p.add_argument(
        "--duration",
        type=float,
        default=30.0,
        help="minimum window; extended automatically to cover the "
        "scenario's last heal plus the liveness bound",
    )
    p.add_argument(
        "--timeout-delay",
        type=int,
        default=1_000,
        help="consensus timeout (ms) — chaos runs default lower than "
        "`local` so view changes during outages resolve quickly",
    )
    p.add_argument("--verifier", choices=["cpu", "tpu", "tpu-sharded", "mesh"], default="cpu")
    p.add_argument("--transport", choices=["asyncio", "native"], default="asyncio")
    p.add_argument(
        "--journal",
        action="store_true",
        help="flight recorder on: fault windows appear as spans on the "
        "chaos-plane track of logs/trace.json",
    )
    p.add_argument(
        "--health",
        action="store_true",
        help="health plane on in every node (see `local --health`); "
        "detector firings land in the + HEALTH SUMMARY block and, with "
        "--journal, on the incidents track of logs/trace.json",
    )
    p.set_defaults(fn=task_chaos)

    p = sub.add_parser("tpu")
    p.add_argument("--sizes", default="4,8,16")
    p.add_argument("--rate", type=int, default=1_000)
    p.add_argument("--duration", type=float, default=20.0)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--timeout-delay", type=int, default=5_000)
    p.set_defaults(fn=task_tpu)

    p = sub.add_parser(
        "profile",
        help="verify-pipeline span waterfall: where a QC verify wave's "
        "wall time goes, stage by stage (docs/TELEMETRY.md)",
    )
    p.add_argument("--sizes", default="16,64,256", help="QC sizes to profile")
    p.add_argument("--waves", type=int, default=20)
    p.add_argument(
        "--verifier",
        choices=["cpu", "tpu", "tpu-sharded", "mesh", "bls"],
        default="tpu",
        help="bls = the BLS claims path (device G1 aggregation + host "
        "pairing equality per QC)",
    )
    p.add_argument(
        "--train",
        type=int,
        default=0,
        metavar="N",
        help="sustained wave-train mode: N distinct-digest waves back "
        "to back through the dispatch pipeline (largest --sizes entry), "
        "amortized per-wave latency + overlap efficiency at depth 1 vs "
        "HOTSTUFF_VERIFY_PIPELINE",
    )
    p.add_argument(
        "--route",
        choices=["device", "auto"],
        default="device",
        help="device = pin warmed-up waves to the device "
        "(HOTSTUFF_FORCE_DEVICE_ROUTE); auto = adaptive cost-model "
        "routing as in production",
    )
    p.add_argument(
        "--capture",
        default=None,
        metavar="DIR",
        help="wrap the largest size's waves in jax.profiler.trace(DIR) "
        "for XLA-op-level inspection",
    )
    p.set_defaults(fn=task_profile)

    p = sub.add_parser("scaling")
    p.add_argument("--sizes", default="4,8,16,32")
    p.add_argument("--rate", type=int, default=1_000)
    p.add_argument("--duration", type=float, default=20.0)
    p.add_argument(
        "--verifier", choices=["cpu", "tpu", "tpu-sharded", "mesh"], default="cpu"
    )
    p.set_defaults(fn=task_scaling)

    p = sub.add_parser("storm")
    p.add_argument("--nodes", type=int, default=256)
    p.add_argument(
        "--device", action="store_true", help="also run the TPU backend"
    )
    p.add_argument("--no-bls", action="store_true")
    p.set_defaults(fn=task_storm)

    p = sub.add_parser("logs")
    p.add_argument("--dir", default=PathMaker.logs_path())
    p.set_defaults(fn=task_logs)

    p = sub.add_parser(
        "explore",
        help="seeded schedule sweep through the deterministic "
        "virtual-time simulator: whole committee in one process, "
        "invariant verdict per seed, repro bundle + shrunk minimal "
        "schedule on failure (docs/SIM.md)",
    )
    p.add_argument("--seeds", type=int, default=100,
                   help="number of consecutive seeds to run")
    p.add_argument("--start", type=int, default=0, help="first seed")
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument(
        "--duration",
        type=float,
        default=None,
        help="virtual seconds per run (default: schedule-drawn; "
        "HOTSTUFF_SIM_DURATION)",
    )
    p.add_argument(
        "--out",
        default=None,
        metavar="DIR",
        help="directory for failure repro bundles "
        "(default: <logs>/sim-explore)",
    )
    p.add_argument(
        "--no-shrink",
        action="store_true",
        help="skip greedy schedule shrinking on failure",
    )
    p.add_argument(
        "--guided",
        action="store_true",
        help="fitness-guided search (adaptive adversaries + schedule "
        "mutation across generations) at the same run budget as the "
        "flat sweep; threatening schedules are shrunk and promoted",
    )
    p.add_argument(
        "--corpus",
        default=None,
        metavar="FILE",
        help="with --guided: append promoted schedules to this "
        "regression corpus (tests/data/sim_seeds.json dialect)",
    )
    p.add_argument(
        "--scenarios-dir",
        default=None,
        metavar="DIR",
        help="with --guided: emit promoted schedules as canned chaos "
        "scenario specs (consumable via `benchmark chaos --spec`)",
    )
    p.set_defaults(fn=task_explore)

    p = sub.add_parser("traces")
    p.add_argument(
        "--dir",
        default=PathMaker.journals_path(),
        help="directory holding the per-node journal segments",
    )
    p.add_argument(
        "--out",
        default=PathMaker.trace_file(),
        help="where to write the Chrome trace-event JSON",
    )
    p.set_defaults(fn=task_traces)

    p = sub.add_parser(
        "critpath",
        help="commit critical-path attribution from a run's journals: "
        "the + CRITPATH block (stage p50/p99, dominant-stage histogram, "
        "regime classification), the Perfetto critical-path track, and "
        "the attribution-diff regression gate (--diff)",
    )
    p.add_argument(
        "--dir",
        default=PathMaker.journals_path(),
        help="directory holding the per-node journal segments",
    )
    p.add_argument(
        "--out",
        default=PathMaker.trace_file(),
        help="where to write the Chrome trace-event JSON "
        "(with the critical-path track)",
    )
    p.add_argument(
        "--diff",
        default=None,
        metavar="REF.json",
        help="reference attribution to gate against (a bench JSON "
        "document or a prior attribution document); exit 1 when any "
        "stage's latency share grew beyond "
        "HOTSTUFF_CRITPATH_DIFF_PP percentage points",
    )
    p.add_argument(
        "--json",
        action="store_true",
        help="also print the attribution as one machine-readable "
        "JSON line",
    )
    p.set_defaults(fn=task_critpath)

    p = sub.add_parser(
        "watch",
        help="live fleet dashboard: scrape every committee node's "
        "/delta endpoint, render per-node round/commit-rate/leader/"
        "route-mix/credit columns and run the fleet anomaly detectors "
        "(committee must be running with --health)",
    )
    p.add_argument(
        "--interval", type=float, default=1.0, help="seconds between ticks"
    )
    p.add_argument(
        "--duration",
        type=float,
        default=0.0,
        help="stop after this many seconds (0 = until interrupted)",
    )
    p.add_argument(
        "--once",
        action="store_true",
        help="render a single frame and exit (no screen clearing)",
    )
    p.add_argument(
        "--timeout-delay",
        type=int,
        default=5_000,
        help="the committee's consensus timeout (ms) — scales the "
        "leader-stall detector's k*timeout threshold",
    )
    p.set_defaults(fn=task_watch)

    p = sub.add_parser("aggregate")
    p.set_defaults(fn=task_aggregate)

    p = sub.add_parser("plot")
    p.set_defaults(fn=task_plot)

    # remote/cluster tasks (reference fabfile.py create/destroy/install/
    # start/stop/info/remote, re-targeted at TPU VMs — benchmark/remote.py)
    for name in ("create", "destroy", "start", "stop", "info", "install",
                 "update", "remote-kill"):
        p = sub.add_parser(name)
        p.add_argument("--settings", default="settings.json")
        p.set_defaults(fn=task_remote_lifecycle, lifecycle=name)

    p = sub.add_parser("remote")
    p.add_argument("--settings", default="settings.json")
    p.add_argument("--sizes", default="4,8")
    p.add_argument("--rates", default="1000")
    p.add_argument("--duration", type=float, default=30.0)
    p.add_argument("--runs", type=int, default=1)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument(
        "--verifier",
        choices=["cpu", "tpu", "tpu-sharded", "mesh"],
        default="tpu",
    )
    p.add_argument(
        "--journal",
        action="store_true",
        help="flight recorder on in every remote node; journal dirs are "
        "pulled per host and merged before the cross-node trace",
    )
    p.add_argument(
        "--profile",
        action="store_true",
        help="verify-pipeline span profiler on in every remote node "
        "(spans land in the pulled journals when --journal is also set)",
    )
    p.add_argument(
        "--fault-plane",
        default=None,
        metavar="SCENARIO_OR_SPEC",
        help="run the sweep under a fault/adversary scenario: a canned "
        "name (split-brain, byz-equivocate, byz-collude, ...) or a spec "
        "JSON path; uploaded with the configs and threaded to every "
        "node via --fault-plane/--adversary",
    )
    p.add_argument(
        "--fault-seed",
        type=int,
        default=0,
        help="seed for a canned --fault-plane scenario",
    )
    p.add_argument(
        "--watch",
        action="store_true",
        help="health plane on in every remote node and a live fleet "
        "dashboard over the instance map during each run; unreachable "
        "nodes show an explicit STALE column instead of hanging the "
        "driver",
    )
    p.set_defaults(fn=task_remote_bench)

    args = parser.parse_args(argv)
    try:
        return args.fn(args)
    except BenchError as e:
        Print.error(str(e))
        return 1


if __name__ == "__main__":
    sys.exit(main())
