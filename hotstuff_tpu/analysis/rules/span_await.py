"""no-await-in-span: a span wraps one synchronous segment.

``spans.span(name, **ids)`` (telemetry/spans.py) lands in the
profiler's own trace while a session is active, and the profiler's
events on one thread must nest.  64 ``Core`` coroutines share the
event-loop thread and interleave at every ``await``: a span held across
one would overlap whatever the loop runs meanwhile — another node's
span, ``loop.idle`` — and the trace's self-time arithmetic
(``chipbench/hostspans.py``) would charge that time twice.  So inside a
``with ...span(...)`` block there is no ``await``, ``async for``,
``async with`` or ``yield``; a wait across one is derived by the
trace's reader from the spans on either side.

Scope is lexical, like no-blocking-in-async: nested ``def``/``lambda``
bodies are not part of the block.
"""

from __future__ import annotations

import ast

from ..framework import Finding, terminal_name, walk_no_nested_functions

RULE = "no-await-in-span"

_SUSPENDS = (ast.Await, ast.AsyncFor, ast.AsyncWith, ast.Yield, ast.YieldFrom)


def _is_span_call(expr) -> bool:
    return isinstance(expr, ast.Call) and terminal_name(expr.func) == "span"


class NoAwaitInSpan:
    name = RULE
    targets = (
        "hotstuff_tpu/consensus/**/*.py",
        "hotstuff_tpu/network/**/*.py",
        "hotstuff_tpu/node/**/*.py",
        "hotstuff_tpu/crypto/**/*.py",
        "hotstuff_tpu/store/**/*.py",
    )

    def check(self, sf, root) -> list[Finding]:
        findings: list[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            spans = [
                item.context_expr
                for item in node.items
                if _is_span_call(item.context_expr)
            ]
            if not spans:
                continue
            first = spans[0].args[0] if spans[0].args else None
            stage = (
                first.value
                if isinstance(first, ast.Constant)
                and isinstance(first.value, str)
                else "<dynamic>"
            )
            held = next(
                (
                    inner
                    for inner in walk_no_nested_functions(node)
                    if isinstance(inner, _SUSPENDS)
                ),
                None,
            )
            if held is not None:
                findings.append(
                    Finding(
                        RULE,
                        sf.rel,
                        held.lineno,
                        f"span:{stage}",
                        f"span '{stage}' is held across a suspension "
                        f"point: the profiler's events on the loop "
                        f"thread must nest, so wrap the synchronous "
                        f"segments on either side instead",
                    )
                )
        return findings
