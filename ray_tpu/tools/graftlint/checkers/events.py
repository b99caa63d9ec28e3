"""GL008 event-record-schema — cluster-event records stay queryable.

The head keeps a cluster-event ring (`_record_event` / MsgType.
RECORD_EVENT) that operators grep during incidents.  Its value depends
on records agreeing on an envelope: severity from the standard set, a
stable lowercase source tag, and ONE timestamp — the one the envelope
stamps.  This rule pins that schema at the call sites:

- ``_record_event(severity, source, message, **fields)``: severity must
  be a literal from {DEBUG, INFO, WARNING, ERROR, CRITICAL}; source must
  be a literal lowercase tag; field names must not collide with the
  envelope (severity/source/message/timestamp) or smuggle a second
  clock (time/date/ts variants) — drifted records sort wrong and split
  dashboards.
- ``conn.send(MsgType.RECORD_EVENT, {...})`` payload literals: same
  severity vocabulary, and "fields" must obey the same key rules.
- flight-recorder phase stamps (_private/task_events.py): a literal
  phase name written into a stamp dict (``ph["..."] = ...`` /
  ``spec.phases["..."] = ...`` / ``task_events.stamp(d, "...")``) must
  come from the canonical ``task_events.PHASES`` vocabulary — a typo'd
  phase silently vanishes from every duration, histogram, and timeline
  sub-span that joins on the canonical names.
- engine-thread profiler spans (serve/tracing.py): a literal
  ``span("...")`` name must come from ``task_events.ENGINE_SPANS`` — the
  trace readers match the same names.

Non-literal arguments are skipped (runtime sanitization in
h_record_event covers them).
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional

from ray_tpu.tools.graftlint.core import (
    FileChecker,
    FileContext,
    Finding,
    Rule,
    register,
)

_SEVERITIES = {"DEBUG", "INFO", "WARNING", "ERROR", "CRITICAL"}
_ENVELOPE = {"severity", "source", "message", "timestamp"}
_CLOCK_DRIFT = {"time", "date", "ts", "datetime", "timestamp_ms", "when"}

# Stamp-dict spellings the phase-vocabulary check binds to.  Narrow on
# purpose: `ph` / `phases` locals and `.phases` attributes are the
# flight-recorder idiom (task_events.py); arbitrary dicts stay unchecked.
_PHASE_DICT_NAMES = {"ph", "phases"}


def _phase_vocabulary() -> set:
    # single source of truth: the canonical tuple in task_events.py
    from ray_tpu._private.task_events import PHASES

    return set(PHASES)


def _const_str(node: Optional[ast.expr]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_record_event_send(node: ast.Call) -> bool:
    if not node.args:
        return False
    first = node.args[0]
    return (
        isinstance(first, ast.Attribute)
        and first.attr == "RECORD_EVENT"
        and isinstance(first.value, ast.Name)
        and first.value.id == "MsgType"
    )


@register
class EventRecordSchemaChecker(FileChecker):
    rule = Rule(
        "GL008",
        "event-record-schema",
        "cluster-event records: canonical severity, stable source, one clock",
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                yield from self._check_phase_stamp_targets(ctx, node)
                continue
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else getattr(func, "id", "")
            if name == "_record_event" or name == "record_event":
                yield from self._check_direct(ctx, node)
            elif name in ("send", "request") and _is_record_event_send(node):
                yield from self._check_wire(ctx, node)
            elif name == "stamp" and len(node.args) >= 2:
                yield from self._check_phase_name(ctx, node, _const_str(node.args[1]))
            elif name == "span" and node.args:
                yield from self._check_span_name(ctx, node, _const_str(node.args[0]))

    @staticmethod
    def _is_phase_dict(base: ast.expr) -> bool:
        if isinstance(base, ast.Name):
            return base.id in _PHASE_DICT_NAMES
        return isinstance(base, ast.Attribute) and base.attr == "phases"

    def _check_phase_stamp_targets(self, ctx: FileContext, node: ast.Assign) -> Iterator[Finding]:
        for target in node.targets:
            if not isinstance(target, ast.Subscript):
                continue
            if not self._is_phase_dict(target.value):
                continue
            yield from self._check_phase_name(ctx, target, _const_str(target.slice))

    def _check_phase_name(self, ctx: FileContext, node, phase) -> Iterator[Finding]:
        if phase is None:
            return  # non-literal: the runtime vocabulary owns it
        vocab = _phase_vocabulary()
        if phase not in vocab:
            yield ctx.finding(
                self.rule,
                node,
                f"phase stamp {phase!r} is not in the canonical "
                f"task_events.PHASES vocabulary {sorted(vocab)}: a drifted "
                "name drops out of every duration/histogram/timeline join",
            )

    def _check_span_name(self, ctx: FileContext, node, name) -> Iterator[Finding]:
        from ray_tpu._private.task_events import ENGINE_SPANS

        if name is not None and name not in ENGINE_SPANS:
            yield ctx.finding(
                self.rule,
                node,
                f"span {name!r} is not in task_events.ENGINE_SPANS "
                f"{sorted(ENGINE_SPANS)}: a reader of the profiler trace "
                "matches spans by these names",
            )

    def _check_direct(self, ctx: FileContext, node: ast.Call) -> Iterator[Finding]:
        sev = _const_str(node.args[0]) if node.args else None
        if sev is not None and sev not in _SEVERITIES:
            yield ctx.finding(
                self.rule,
                node,
                f"event severity {sev!r} is not one of {sorted(_SEVERITIES)}: "
                "drifted severities split dashboards and alert filters",
            )
        src = _const_str(node.args[1]) if len(node.args) > 1 else None
        if src is not None and (not src or src != src.lower() or " " in src):
            yield ctx.finding(
                self.rule,
                node,
                f"event source {src!r} must be a stable lowercase tag "
                "(e.g. 'node', 'actor', 'object_store')",
            )
        for kw in node.keywords:
            if kw.arg is None:
                continue
            if kw.arg in _ENVELOPE or kw.arg.lower() in _CLOCK_DRIFT:
                yield ctx.finding(
                    self.rule,
                    node,
                    f"event field {kw.arg!r} collides with the envelope or "
                    "carries a second clock; the envelope owns the timestamp",
                )

    def _check_wire(self, ctx: FileContext, node: ast.Call) -> Iterator[Finding]:
        payload = node.args[1] if len(node.args) > 1 else None
        if not isinstance(payload, ast.Dict):
            return
        entries = {
            _const_str(k): v for k, v in zip(payload.keys, payload.values) if k
        }
        sev = _const_str(entries.get("severity"))
        if sev is not None and sev not in _SEVERITIES:
            yield ctx.finding(
                self.rule,
                node,
                f"RECORD_EVENT severity {sev!r} is not one of "
                f"{sorted(_SEVERITIES)}",
            )
        for required in ("severity", "source", "message"):
            if required not in entries:
                yield ctx.finding(
                    self.rule,
                    node,
                    f"RECORD_EVENT payload is missing {required!r}: the head "
                    "fills a default and the record loses its provenance",
                )
        fields = entries.get("fields")
        if isinstance(fields, ast.Dict):
            for k in fields.keys:
                ks = _const_str(k)
                if ks is not None and (
                    ks in _ENVELOPE or ks.lower() in _CLOCK_DRIFT
                ):
                    yield ctx.finding(
                        self.rule,
                        node,
                        f"RECORD_EVENT field {ks!r} collides with the "
                        "envelope or carries a second clock",
                    )
