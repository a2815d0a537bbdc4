"""The one process that holds the chip: the committee, through the
program's normal entry point.

It names the device, traces it and reads its memory when ``run.py``
asks (by files in the run directory: only the chip's holder can do
either), and otherwise only calls
``hotstuff_tpu.node.main.main(["run-many", ...])`` with the arguments
``benchmark/local.py`` gives it.  Without a TPU it fails (exit 3), and
so it does, before it imports jax, where the program lacks a part that
the configuration ``needs`` (exit 4); ``--dry`` is the CPU rehearsal of
the tests, prints ``platform: cpu`` and is never a cell.  It does not
outlive ``run.py`` (``ending.die_with_parent``).
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_json(path: str, data) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(data, f)
    os.replace(path + ".tmp", path)


def write_committee(run_dir: str, config: dict, seed: int) -> list[str]:
    """Keys from the seed, committee and parameters as
    ``benchmark/local.py`` writes them, for either scheme: a BLS key
    carries its proof of possession, in its key file and in the
    committee (``Consensus.spawn`` refuses a BLS committee without).
    Returns the key files."""
    from benchmark.local import safe_base_port
    from hotstuff_tpu.consensus import Committee, Parameters
    from hotstuff_tpu.crypto.scheme import bls_pop, keygen_deterministic
    from hotstuff_tpu.node.config import (
        Secret,
        write_committee as write_committee_file,
        write_parameters,
    )

    scheme = config["scheme"]
    key_seed = hashlib.sha256(f"chipbench keys {seed}".encode()).digest()
    secrets = []
    for i in range(config["nodes"]):
        name, secret = keygen_deterministic(scheme, key_seed, i)
        pop = bls_pop(secret.to_bytes()) if scheme == "bls" else None
        secrets.append(Secret(name, secret, scheme, pop))
    base_port = safe_base_port()
    committee = Committee.new(
        [
            (secret.name, 1, ("127.0.0.1", base_port + i))
            for i, secret in enumerate(secrets)
        ],
        scheme=scheme,
        pops={s.name: s.pop for s in secrets if s.pop is not None},
    )
    write_committee_file(committee, os.path.join(run_dir, "committee.json"))
    write_parameters(
        Parameters(
            timeout_delay=config["timeout_delay_ms"],
            sync_retry_delay=config["sync_retry_delay_ms"],
        ),
        os.path.join(run_dir, "parameters.json"),
    )
    key_files = []
    for i, secret in enumerate(secrets):
        key_files.append(os.path.join(run_dir, f"node_{i}.json"))
        secret.write(key_files[-1])
    # the order run.py's generator knows the nodes by: that of the keys
    write_json(
        os.path.join(run_dir, "nodes.json"),
        [
            {"name": str(s.name)[:8], "address": ["127.0.0.1", base_port + i]}
            for i, s in enumerate(secrets)
        ],
    )
    return key_files


def missing_need(needs: list[str]) -> str | None:
    """The first of a configuration's ``needs``, each a
    ``"package.module:attribute"`` of the program (``Class.method``
    for a method), that this program does not have: the name of the
    thing is the capability."""
    for need in needs:
        module, _, attribute = need.partition(":")
        try:
            found = importlib.import_module(module)
            for part in attribute.split("."):
                found = getattr(found, part)
        except (ImportError, AttributeError):
            return need
    return None


def serve_requests(run_dir: str, jax) -> None:
    """Answer ``run.py``: ``trace.request`` (seconds to trace) gets a
    profiler trace under ``trace/`` and ``trace.done``; ``memory.request``
    gets ``memory.json``."""
    trace_request = os.path.join(run_dir, "trace.request")
    memory_request = os.path.join(run_dir, "memory.request")
    traced = False
    while True:
        if not traced and os.path.exists(trace_request):
            traced = True
            with open(trace_request) as f:
                seconds = float(f.read())
            options = jax.profiler.ProfileOptions()
            # the device's events are what is read; tracing every Python
            # call of 64 nodes would slow the very rounds it looks at
            options.python_tracer_level = 0
            started = time.time()
            jax.profiler.start_trace(
                os.path.join(run_dir, "trace"), profiler_options=options
            )
            time.sleep(seconds)
            stopped = time.time()
            jax.profiler.stop_trace()
            write_json(
                os.path.join(run_dir, "trace.done"),
                {"started": started, "stopped": stopped},
            )
        if os.path.exists(memory_request):
            peaks = [
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices()
            ]
            write_json(
                os.path.join(run_dir, "memory.json"),
                {"memory_peak_bytes": max(peaks)},
            )
            return
        time.sleep(0.05)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--run-dir", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--dry", action="store_true")
    args = parser.parse_args()
    sys.path.insert(0, ROOT)
    from chipbench import ending

    ending.die_with_parent()
    with open(args.config) as f:
        config = json.load(f)
    # a tree that cannot run the deployment says so at once: before jax
    # is imported, the chip opened or a file written
    lacks = missing_need(config.get("needs", []))
    if lacks is not None:
        print(
            f"chipbench: configuration {config['name']} needs {lacks}, "
            "which this program does not have",
            file=sys.stderr,
        )
        return 4

    import jax

    # the repo's one compile-cache rule (fixed path inside the checkout,
    # or where JAX_COMPILATION_CACHE_DIR says) is applied by this import
    from hotstuff_tpu.tpu import device_info

    device = device_info()
    if not args.dry and (
        device["platform"] != "tpu" or device["count"] < args.chips
    ):
        print(
            f"chipbench: needs {args.chips} TPU chip(s), jax found {device}",
            file=sys.stderr,
        )
        return 3
    write_json(os.path.join(args.run_dir, "device.json"), device)
    key_files = write_committee(args.run_dir, config, args.seed)
    threading.Thread(
        target=serve_requests, args=(args.run_dir, jax), daemon=True
    ).start()

    from hotstuff_tpu.node.main import main as node_main

    return node_main(
        [
            "-vv",
            "run-many",
            "--keys",
            ",".join(key_files),
            "--committee",
            os.path.join(args.run_dir, "committee.json"),
            "--store-prefix",
            os.path.join(args.run_dir, ".db_"),
            "--parameters",
            os.path.join(args.run_dir, "parameters.json"),
            "--verifier",
            "cpu" if args.dry else config["verifier"],
            "--transport",
            config["transport"],
        ]
    )


if __name__ == "__main__":
    sys.exit(main())
