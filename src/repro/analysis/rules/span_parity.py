"""span-parity: every span kind emitted in src must be in SPAN_SCHEMA and
pinned by the test suite; so must every wall-clock span and counter name.

The observability contract (repro.obs): emitters pass the span ``kind`` as
a string literal from :data:`repro.obs.tracing.SPAN_SCHEMA`, so the whole
span vocabulary is statically enumerable.  This rule enforces the three
halves of that contract:

  * a ``Tracer.add_span`` / ``open_span`` / ``event`` call whose kind
    argument is NOT a string literal defeats static auditing — finding at
    the call site;
  * a literal kind that is missing from the schema table would raise at
    runtime (the tracer validates) but should be caught at lint time —
    finding at the call site;
  * a kind emitted somewhere in src but never named in any scanned test
    file has no behavioural pin (nothing fails if its emission silently
    disappears) — finding anchored at the obs test file, mirroring
    registry-parity.

The same three checks hold for the wall-clock recorder
(:mod:`repro.obs.hostspans`): the name passed to ``hostspans.span(...)``
or ``hostspans.tally(...)`` is a string literal, a key of
:data:`repro.obs.hostspans.HOST_SPAN_SCHEMA`, and named in a test.  Call
sites reach the recorder through the module name ``hostspans``.

Like registry-parity, the rule stays silent about test pins when no test
files were scanned (e.g. ``python -m repro.analysis src``).
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from ..framework import FileContext, Finding, ProjectContext, Rule, register_rule

# Tracer emission methods whose second positional argument is a span kind.
_EMIT_METHODS = ("add_span", "open_span", "event")
# hostspans functions whose first positional argument is a wall-clock name.
_HOST_MODULE = "hostspans"
_HOST_METHODS = ("span", "tally")


def _live_schema() -> Tuple[str, ...]:
    from repro.obs.tracing import SPAN_SCHEMA

    return tuple(SPAN_SCHEMA)


def _live_host_schema() -> Tuple[str, ...]:
    from repro.obs.hostspans import HOST_SPAN_SCHEMA

    return tuple(HOST_SPAN_SCHEMA)


def _kind_arg(call: ast.Call) -> Optional[ast.expr]:
    """The span-kind argument of an emission call: positional #2
    (after tid) or the ``kind=`` keyword."""
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "kind":
            return kw.value
    return None


def _host_name_arg(call: ast.Call) -> Optional[ast.expr]:
    """The name argument of a ``hostspans.span``/``tally`` call, or None
    when the call is not one."""
    func = call.func
    if not (isinstance(func, ast.Attribute) and func.attr in _HOST_METHODS
            and isinstance(func.value, ast.Name)
            and func.value.id == _HOST_MODULE):
        return None
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "name":
            return kw.value
    return None


@register_rule
class SpanParityRule(Rule):
    name = "span-parity"
    severity = "error"
    description = (
        "every span kind emitted via Tracer.add_span/open_span/event, and "
        "every name passed to hostspans.span/tally, must be a string "
        "literal, present in SPAN_SCHEMA / HOST_SPAN_SCHEMA, and named in "
        "the scanned test suite (repro.obs contract)"
    )
    default_paths = ("",)
    TEST_PATHS_OPTION = "test_paths"      # prefixes that count as test files
    SRC_PATHS_OPTION = "src_paths"        # prefixes whose emissions are audited
    SCHEMA_OPTION = "schema"              # schema override (fixtures)
    HOST_SCHEMA_OPTION = "host_schema"    # HOST_SPAN_SCHEMA override

    def _test_paths(self) -> Tuple[str, ...]:
        return tuple(self.options.get(self.TEST_PATHS_OPTION, ("tests",)))

    def _src_paths(self) -> Tuple[str, ...]:
        return tuple(self.options.get(self.SRC_PATHS_OPTION, ("src",)))

    def check_file(self, ctx: FileContext, project: ProjectContext
                   ) -> Iterator[Finding]:
        if any(ctx.path.startswith(p) for p in self._test_paths()):
            literals: Set[str] = project.store.setdefault(
                "span_test_literals", set())  # type: ignore[assignment]
            test_files: List[str] = project.store.setdefault(
                "span_test_files", [])  # type: ignore[assignment]
            test_files.append(ctx.path)
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Constant) and isinstance(node.value, str):
                    literals.add(node.value)
        if not any(ctx.path.startswith(p) for p in self._src_paths()):
            return
        emits: List[Tuple[str, str, int]] = project.store.setdefault(
            "span_emits", [])  # type: ignore[assignment]
        host_emits: List[Tuple[str, str, int]] = project.store.setdefault(
            "host_span_emits", [])  # type: ignore[assignment]
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _host_name_arg(node)
            if name is not None:
                if not (isinstance(name, ast.Constant)
                        and isinstance(name.value, str)):
                    yield self.finding(
                        ctx, node,
                        f"name passed to hostspans.{node.func.attr}() must "
                        "be a string literal from HOST_SPAN_SCHEMA — a "
                        "computed name defeats the static span audit",
                    )
                else:
                    host_emits.append((name.value, ctx.path, node.lineno))
                continue
            if not (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _EMIT_METHODS):
                continue
            kind = _kind_arg(node)
            if kind is None:
                continue
            if not (isinstance(kind, ast.Constant)
                    and isinstance(kind.value, str)):
                yield self.finding(
                    ctx, node,
                    f"span kind passed to .{node.func.attr}() must be a "
                    "string literal from SPAN_SCHEMA — a computed kind "
                    "defeats the static span audit",
                )
                continue
            emits.append((kind.value, ctx.path, node.lineno))

    def finalize(self, project: ProjectContext) -> Iterator[Finding]:
        test_files: List[str] = project.store.get(
            "span_test_files", [])  # type: ignore[assignment]
        literals: Set[str] = project.store.get(
            "span_test_literals", set())  # type: ignore[assignment]
        audits = (
            ("span_emits", self.SCHEMA_OPTION, _live_schema,
             "repro.obs.tracing.SPAN_SCHEMA", "span kind"),
            ("host_span_emits", self.HOST_SCHEMA_OPTION, _live_host_schema,
             "repro.obs.hostspans.HOST_SPAN_SCHEMA", "host span name"),
        )
        for store_key, option, live, where, what in audits:
            emits: List[Tuple[str, str, int]] = project.store.get(
                store_key, [])  # type: ignore[assignment]
            if not emits:
                continue
            schema = self.options.get(option)
            if schema is None:
                try:
                    schema = live()
                except Exception as e:  # schema unimportable in this env
                    yield self.finding(
                        emits[0][1], emits[0][2],
                        f"could not import {where} to cross-check emitted "
                        f"names: {e!r}",
                    )
                    continue
            schema = tuple(schema)
            table = where.rsplit(".", 1)[1]
            for kind, path, line in emits:
                if kind not in schema:
                    yield self.finding(
                        path, line,
                        f"{what} {kind!r} is not in {table} — add it to "
                        "the schema table (and obs/README.md) or fix the "
                        "typo",
                    )
            if not test_files:
                continue
            anchor = self._anchor(test_files)
            for kind in sorted({k for k, _, _ in emits}):
                if kind in schema and kind not in literals:
                    yield self.finding(
                        anchor, 1,
                        f"{what} {kind!r} is emitted in src but never named "
                        "in the scanned test suite — it has no behavioural "
                        "pin (add it to the obs suite)",
                    )

    @staticmethod
    def _anchor(test_files: List[str]) -> str:
        for path in test_files:
            if "test_obs" in path:
                return path
        return test_files[0]
