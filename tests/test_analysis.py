"""The static analysis plane (ISSUE 12): every rule catches its
synthetic violation, respects ``# lint: allow``, the allowlist
round-trips with stale detection, and the real tree passes clean.

Fixture trees are written under ``tmp_path`` at the repo-relative paths
each rule targets, so the tests exercise the same glob/targeting logic
the LINT=1 gate uses.  Everything here is stdlib-only — no jax, no
node runtime — by the analysis plane's own design constraint.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap

import pytest

from hotstuff_tpu.analysis import (
    Finding,
    load_allowlist,
    run_rules,
)
from hotstuff_tpu.analysis import knobgen
from hotstuff_tpu.analysis.framework import apply_allowlist, repo_root
from hotstuff_tpu.analysis.rules import ALL_RULES
from hotstuff_tpu.analysis.rules.blocking import NoBlockingInAsync
from hotstuff_tpu.analysis.rules.env_knobs import EnvKnobRegistry
from hotstuff_tpu.analysis.rules.guarded_by import GuardedBy
from hotstuff_tpu.analysis.rules.span_await import NoAwaitInSpan
from hotstuff_tpu.analysis.rules.taxonomy_rule import TaxonomyRegistry
from hotstuff_tpu.analysis.rules.wire_bounds import WireDecoderBounds


def _tree(tmp_path, files: dict) -> str:
    """Write ``{repo-relative path: source}`` under tmp_path."""
    for rel, text in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _codes(findings) -> set:
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# no-blocking-in-async


def test_blocking_rule_catches_sync_calls_in_async_def(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": """\
                import time


                async def propose(self, fut, sock):
                    time.sleep(0.1)
                    stake = fut.result()
                    value = self.store.engine.get(b"k")
                    data = sock.recv(1024)
                    return stake, value, data
                """,
        },
    )
    findings = run_rules([NoBlockingInAsync()], root)
    assert _codes(findings) == {
        "time.sleep",
        "fut.result",
        "self.store.engine.get",
        "sock.recv",
    }
    assert all(f.rule == "no-blocking-in-async" for f in findings)


@pytest.mark.parametrize("method", ["put_many", "get_many"])
def test_blocking_rule_knows_the_engines_batch_calls(tmp_path, method):
    """A write batch or a batched read on an engine blocks like a put:
    an actor goes through the Store (``write_many``), not the engine."""
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": f"""\
                async def persist(self, records):
                    self.store.engine.{method}(records)
                    await self.store.write_many(records)
                """,
        },
    )
    findings = run_rules([NoBlockingInAsync()], root)
    assert _codes(findings) == {f"self.store.engine.{method}"}


def test_blocking_rule_ignores_sync_defs_and_nested_functions(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": """\
                import time


                def sync_helper():
                    time.sleep(1)  # sync context: out of scope


                async def run(self, loop):
                    def callback():
                        time.sleep(1)  # nested def: different schedule

                    await loop.run_in_executor(None, callback)
                    result = await self.task  # awaited, not blocking
                    return result
                """,
        },
    )
    assert run_rules([NoBlockingInAsync()], root) == []


def test_blocking_rule_respects_inline_allow(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": """\
                async def tally(done):
                    total = 0
                    for t in done:
                        # t is in asyncio.wait's done set: result() is an
                        # immediate read, never a block
                        # lint: allow(no-blocking-in-async)
                        total += t.result()
                    return total
                """,
        },
    )
    assert run_rules([NoBlockingInAsync()], root) == []


def test_allow_marker_works_anywhere_in_comment_block(tmp_path):
    # the marker ABOVE the justification lines, not adjacent to the code
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": """\
                async def tally(t):
                    # lint: allow(no-blocking-in-async)
                    # a multi-line justification sits between the marker
                    # and the flagged call; the contiguous block carries it
                    return t.result()
                """,
        },
    )
    assert run_rules([NoBlockingInAsync()], root) == []


def test_allow_for_a_different_rule_does_not_suppress(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/actor.py": """\
                async def tally(t):
                    # lint: allow(wire-decoder-bounds)
                    return t.result()
                """,
        },
    )
    assert _codes(run_rules([NoBlockingInAsync()], root)) == {"t.result"}


# ---------------------------------------------------------------------------
# wire-decoder-bounds


def test_wire_bounds_catches_unbounded_count(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/wire.py": """\
                def decode_votes(dec):
                    n = dec.u32()
                    return [dec.raw(64) for _ in range(n)]
                """,
        },
    )
    findings = run_rules([WireDecoderBounds()], root)
    assert _codes(findings) == {"decode_votes:n"}


def test_wire_bounds_accepts_bounded_count(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/wire.py": """\
                MAX = 4096


                def decode_votes(dec):
                    n = dec.u32()
                    if n > MAX:
                        raise ValueError("vote count over cap")
                    return [dec.raw(64) for _ in range(n)]
                """,
        },
    )
    assert run_rules([WireDecoderBounds()], root) == []


def test_wire_bounds_equality_check_is_not_a_bound(tmp_path):
    # ``n == SENTINEL`` routes a format variant; it bounds nothing
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/wire.py": """\
                SENTINEL = 0xFFFFFFFF


                def decode_votes(dec):
                    n = dec.u32()
                    if n == SENTINEL:
                        return None
                    return [dec.raw(64) for _ in range(n)]
                """,
        },
    )
    assert _codes(run_rules([WireDecoderBounds()], root)) == {
        "decode_votes:n"
    }


def test_wire_bounds_flags_uncapped_var_bytes(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/wire.py": """\
                def decode_blob(dec):
                    return dec.var_bytes()
                """,
        },
    )
    assert _codes(run_rules([WireDecoderBounds()], root)) == {
        "decode_blob:var_bytes"
    }


def test_wire_bounds_accepts_capped_var_bytes(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/wire.py": """\
                def decode_blob(dec):
                    return dec.var_bytes(1024)
                """,
        },
    )
    assert run_rules([WireDecoderBounds()], root) == []


# ---------------------------------------------------------------------------
# taxonomy-registry (fixture trees carry no taxonomy.py, so the rule
# falls back to the real repo's registry)


def test_taxonomy_rule_catches_unregistered_edge_and_stage(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/core.py": """\
                def on_commit(self, j, rec, block, t0, dur):
                    j.record("commmit", block.digest())  # typo
                    j.record("commit", block.digest())   # registered
                    rec.add("dispatch.typo", t0, dur)    # unregistered
                    rec.add("dispatch", t0, dur)         # registered
                """,
        },
    )
    findings = run_rules([TaxonomyRegistry()], root)
    assert _codes(findings) == {"edge:commmit", "stage:dispatch.typo"}


# no-await-in-span: the profiler's events on the loop thread must nest


def test_span_await_rule_catches_a_span_held_across_a_suspension(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/core.py": """                async def vote(self, block):
                    with spans.span("core.vote", node=self._node):
                        await self.store.write(b"k", b"v")
                    with _spans.span("core.commit"):
                        async with self.lock:
                            pass
                    with _spans.span("core.claims"), open("f"):
                        async for _ in self.queue:
                            pass

                def staged(self):
                    with _spans.span("prepare"):
                        yield 1
                """,
            "hotstuff_tpu/store/engine.py": """                async def fine(self, rec):
                    with spans.span("store.write", node=self.node):
                        self.engine.put(b"k", b"v")

                        async def later():  # its own schedule
                            await self.flush()
                    await self.flush()
                    with self.lock:
                        await self.flush()  # not a span
                    with rec.span("dispatch"):
                        return 1
                """,
        },
    )
    findings = run_rules([NoAwaitInSpan()], root)
    assert _codes(findings) == {
        "span:core.vote", "span:core.commit", "span:core.claims",
        "span:prepare",
    }
    assert {f.path for f in findings} == {"hotstuff_tpu/consensus/core.py"}


def test_span_await_rule_covers_every_layer_with_span_sites():
    """consensus, network, node, crypto and store hold the spans that
    share the event-loop thread; the rule looks at all of them."""
    covered = {t.split("/")[1] for t in NoAwaitInSpan.targets}
    assert covered == {"consensus", "network", "node", "crypto", "store"}


def test_every_span_site_uses_a_registered_stage():
    """Each stage name a ``span(...)`` call site of the tree passes is
    in ``telemetry/taxonomy.py``; the host stages are there with the
    prefix of their layer, and ``queue.wait`` (no emitter) is gone."""
    from hotstuff_tpu.telemetry import taxonomy

    findings = run_rules([TaxonomyRegistry()], repo_root())
    assert [f.render() for f in findings] == []
    assert "queue.wait" not in taxonomy.SPAN_STAGES
    prefixes = {name.split(".")[0] for name in taxonomy.SPAN_HOST_STAGES}
    assert prefixes == {
        "core", "proposer", "net", "store", "ingest", "verify", "loop"
    }
    assert set(taxonomy.SPAN_HOST_STAGES) <= taxonomy.SPAN_STAGES
    assert not set(taxonomy.SPAN_HOST_STAGES) & set(taxonomy.SPAN_LEAF_STAGES)


def test_taxonomy_rule_dynamic_edges_need_registered_prefix(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/faults_like.py": "",
            "hotstuff_tpu/consensus/core.py": """\
                def on_fault(self, j, kind):
                    j.record(f"fault.{kind}", None)  # registered prefix
                    j.record(f"byz.{kind}", None)    # registered prefix
                    j.record(f"oops.{kind}", None)   # unregistered
                """,
        },
    )
    findings = run_rules([TaxonomyRegistry()], root)
    assert _codes(findings) == {"edge:<dynamic>"}
    assert len(findings) == 1


def test_taxonomy_rule_ignores_non_journal_receivers(tmp_path):
    # .record() on something that is not a journal handle is out of
    # scope — only the conventional receiver names are checked
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/consensus/core.py": """\
                def run(self, metrics):
                    metrics.record("whatever.metric", 1)
                """,
        },
    )
    assert run_rules([TaxonomyRegistry()], root) == []


# ---------------------------------------------------------------------------
# env-knob-registry + knobgen


_KNOB_TREE = {
    "hotstuff_tpu/__init__.py": """\
        import os

        WINDOW = int(os.environ.get("HOTSTUFF_FIXTURE_WINDOW", "64"))
        """,
}


def test_env_knob_rule_flags_missing_and_stale_docs(tmp_path):
    root = _tree(tmp_path, _KNOB_TREE)
    findings = run_rules([EnvKnobRegistry()], root)
    assert _codes(findings) == {"missing"}

    # regenerating clears the finding
    knobgen.write(root)
    assert run_rules([EnvKnobRegistry()], root) == []

    # a new knob read makes the committed table stale
    extra = tmp_path / "hotstuff_tpu" / "extra.py"
    extra.write_text(
        'import os\nN = int(os.getenv("HOTSTUFF_FIXTURE_NEW", "8"))\n'
    )
    findings = run_rules([EnvKnobRegistry()], root)
    assert _codes(findings) == {"stale"}


def test_knobgen_discovers_helper_routed_and_subscript_reads(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/knobs.py": """\
                import os


                def _env_int(name, default):
                    return int(os.environ.get(name, str(default)))


                A = _env_int("HOTSTUFF_FIXTURE_HELPER", 512)
                B = os.environ["HOTSTUFF_FIXTURE_SUBSCRIPT"]
                C = "HOTSTUFF_FIXTURE_MEMBER" in os.environ
                """,
        },
    )
    knobs = knobgen.scan(root)
    assert set(knobs) == {
        "HOTSTUFF_FIXTURE_HELPER",
        "HOTSTUFF_FIXTURE_SUBSCRIPT",
        "HOTSTUFF_FIXTURE_MEMBER",
    }
    assert knobs["HOTSTUFF_FIXTURE_HELPER"]["defaults"] == ["512"]
    rendered = knobgen.render(root)
    assert "HOTSTUFF_FIXTURE_SUBSCRIPT" in rendered
    assert "3 knobs registered." in rendered


def test_committed_knobs_doc_is_fresh():
    """docs/KNOBS.md matches the real tree — the same invariant the
    gate enforces, asserted here so a stale table fails tier-1 too."""
    assert knobgen.is_fresh(repo_root())


# ---------------------------------------------------------------------------
# guarded-by


_RACY_CLASS = """\
    import threading


    class Service:
        def __init__(self):
            self.count = 0
            self._thread = threading.Thread(target=self._worker)

        def _worker(self):
            self.count += 1

        def snapshot(self):
            return self.count
    """


def test_guarded_by_flags_unannotated_cross_thread_field(tmp_path):
    root = _tree(tmp_path, {"hotstuff_tpu/telemetry/svc.py": _RACY_CLASS})
    findings = run_rules([GuardedBy()], root)
    assert _codes(findings) == {"Service.count"}


def test_guarded_by_accepts_documented_discipline(tmp_path):
    annotated = _RACY_CLASS.replace(
        "self.count += 1",
        "# guarded-by: gil\n            self.count += 1",
    )
    root = _tree(tmp_path, {"hotstuff_tpu/telemetry/svc.py": annotated})
    assert run_rules([GuardedBy()], root) == []


def test_guarded_by_lockset_checks_annotated_lock(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/telemetry/svc.py": """\
                import threading


                class Service:
                    def __init__(self):
                        self._mu = threading.Lock()
                        self.count = 0
                        self._thread = threading.Thread(target=self._worker)

                    def _worker(self):
                        with self._mu:
                            # guarded-by: _mu
                            self.count += 1

                    def reset(self):
                        self.count = 0  # write without holding _mu
                """,
        },
    )
    findings = run_rules([GuardedBy()], root)
    assert _codes(findings) == {"Service.count:unlocked"}


def test_guarded_by_lockset_passes_when_all_writes_hold_lock(tmp_path):
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/telemetry/svc.py": """\
                import threading


                class Service:
                    def __init__(self):
                        self._mu = threading.Lock()
                        self.count = 0
                        self._thread = threading.Thread(target=self._worker)

                    def _worker(self):
                        with self._mu:
                            # guarded-by: _mu
                            self.count += 1

                    def reset(self):
                        with self._mu:
                            self.count = 0
                """,
        },
    )
    assert run_rules([GuardedBy()], root) == []


def test_guarded_by_drift_check_without_thread_creation(tmp_path):
    # no visible Thread(): callers thread from outside.  A field written
    # both under and outside the class lock with no annotation is drift.
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/tpu/dev.py": """\
                import threading


                class Cache:
                    def __init__(self):
                        self._mu = threading.Lock()
                        self.slots = {}

                    def insert(self, k, v):
                        with self._mu:
                            self.slots[k] = v

                    def wipe(self):
                        self.slots = {}
                """,
        },
    )
    findings = run_rules([GuardedBy()], root)
    assert _codes(findings) == {"Cache.slots:drift"}


# ---------------------------------------------------------------------------
# framework: syntax errors, allowlist round-trip


def test_unparseable_target_is_its_own_finding(tmp_path):
    root = _tree(
        tmp_path,
        {"hotstuff_tpu/consensus/wire.py": "def broken(:\n"},
    )
    findings = run_rules([WireDecoderBounds()], root)
    assert _codes(findings) == {"syntax-error"}


def test_allowlist_round_trip_and_stale_detection(tmp_path):
    findings = [
        Finding("r", "a.py", 3, "x", "m1"),
        Finding("r", "b.py", 9, "y", "m2"),
    ]
    path = tmp_path / "allowlist.txt"
    path.write_text(
        "# grandfathered\n"
        "\n"
        f"{findings[0].key}\n"
        "r:gone.py:z\n"  # file since fixed: stale
    )
    keys = load_allowlist(str(path))
    assert keys == {"r:a.py:x", "r:gone.py:z"}
    kept, used, stale = apply_allowlist(findings, keys)
    assert [f.key for f in kept] == ["r:b.py:y"]
    assert used == {"r:a.py:x"}
    assert stale == {"r:gone.py:z"}


def test_finding_keys_are_line_number_free():
    a = Finding("r", "p.py", 10, "tok", "m")
    b = Finding("r", "p.py", 99, "tok", "m")
    assert a.key == b.key == "r:p.py:tok"
    assert "10" in a.render() and "[r]" in a.render()


# ---------------------------------------------------------------------------
# the gate itself


def test_real_tree_passes_clean():
    """The merged repo has zero findings after the committed allowlist —
    exactly what ``LINT=1 scripts/trace.sh`` asserts in CI."""
    import os

    root = repo_root()
    findings = run_rules(ALL_RULES, root)
    allow = load_allowlist(
        os.path.join(root, "hotstuff_tpu", "analysis", "allowlist.txt")
    )
    kept, _, stale = apply_allowlist(findings, allow)
    assert kept == [], "\n".join(f.render() for f in kept)
    assert stale == set(), f"stale allowlist entries: {sorted(stale)}"


def test_cli_check_exits_nonzero_on_violation_fixture(tmp_path):
    """Introducing any rule's violation flips the gate to a non-zero
    exit — the ISSUE 12 acceptance demonstration, via the same
    ``python -m hotstuff_tpu.analysis check`` entry the gate runs."""
    root = _tree(
        tmp_path,
        {
            "hotstuff_tpu/__init__.py": "",
            "hotstuff_tpu/consensus/wire.py": """\
                def decode_votes(dec):
                    n = dec.u32()
                    return [dec.raw(64) for _ in range(n)]
                """,
        },
    )
    knobgen.write(root)  # keep the knob rule out of this fixture's way
    dirty = subprocess.run(
        [
            sys.executable, "-m", "hotstuff_tpu.analysis", "check",
            "--root", root,
        ],
        capture_output=True,
        text=True,
        cwd=repo_root(),
    )
    assert dirty.returncode == 1
    assert "wire-decoder-bounds" in dirty.stdout
    assert "FAIL" in dirty.stdout

    # fixing the fixture flips it back to 0
    (tmp_path / "hotstuff_tpu" / "consensus" / "wire.py").write_text(
        textwrap.dedent(
            """\
            def decode_votes(dec):
                n = dec.u32()
                if n > 4096:
                    raise ValueError("over cap")
                return [dec.raw(64) for _ in range(n)]
            """
        )
    )
    clean = subprocess.run(
        [
            sys.executable, "-m", "hotstuff_tpu.analysis", "check",
            "--root", root,
        ],
        capture_output=True,
        text=True,
        cwd=repo_root(),
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "OK: no findings" in clean.stdout
