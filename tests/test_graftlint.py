"""graftlint self-tests: per-rule positive/negative fixtures, suppression
semantics, reporters, and the CLI contract (exit 0 on the shipped tree).

Each rule gets at least one known-violation fixture (must be flagged) and
one known-clean fixture (must pass).  Fixtures are written into tmp_path
with directory names that trigger the scoped rules (gcs/, raylet/, ...).
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from ray_tpu.tools.graftlint import format_json, format_text, lint_paths
from ray_tpu.tools.graftlint.__main__ import main as graftlint_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write(tmp_path, relpath: str, source: str) -> str:
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return str(path)


def rules_in(findings):
    return {f.rule_name for f in findings}


def lint_file(tmp_path, relpath, source, select=None):
    write(tmp_path, relpath, source)
    return lint_paths([str(tmp_path)], select=select)


# --------------------------------------------------------------------- GL001


def test_fork_jax_init_flags_module_scope_import(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/zygote.py",
        """
        import jax

        def spawn():
            return 1
        """,
    )
    assert "fork-jax-init" in rules_in(findings)


def test_fork_jax_init_flags_backend_call(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/worker_main.py",
        """
        def boot():
            import jax

            return jax.devices()
        """,
    )
    assert "fork-jax-init" in rules_in(findings)


def test_fork_jax_init_allows_lazy_import_outside_zygote(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/serialization.py",
        """
        def reduce_array(arr):
            import jax.numpy as jnp

            return jnp.asarray(arr)
        """,
    )
    assert "fork-jax-init" not in rules_in(findings)


def test_fork_jax_init_bans_function_scope_jax_in_zygote(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/zygote.py",
        """
        def preimport():
            import jax  # pre-fork: forbidden even lazily
        """,
    )
    assert "fork-jax-init" in rules_in(findings)


def test_fork_jax_init_ignores_unrelated_files(tmp_path):
    findings = lint_file(tmp_path, "core/model.py", "import jax\n")
    assert "fork-jax-init" not in rules_in(findings)


# --------------------------------------------------------------------- GL002


def test_loop_blocking_flags_sleep_in_async(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/handlers.py",
        """
        import time

        async def h_thing(p):
            time.sleep(1)
            return {}
        """,
    )
    assert "loop-blocking-call" in rules_in(findings)


def test_loop_blocking_flags_fsync_and_open(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/agent.py",
        """
        import os

        async def persist(f, path):
            os.fsync(f.fileno())
            with open(path) as fh:
                return fh.read()
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "loop-blocking-call") == 2


def test_loop_blocking_allows_async_sleep_and_executor_thunks(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/handlers.py",
        """
        import asyncio
        import time

        def sync_path():
            time.sleep(1)  # fine: not on the loop

        async def h_thing(p):
            await asyncio.sleep(1)

            def _thunk():
                time.sleep(1)  # fine: runs in an executor

            await asyncio.get_running_loop().run_in_executor(None, _thunk)
        """,
    )
    assert "loop-blocking-call" not in rules_in(findings)


# --------------------------------------------------------------------- GL003


def test_silent_except_flags_swallow(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/state.py",
        """
        def load():
            try:
                return 1
            except Exception:
                pass
        """,
    )
    assert "silent-except" in rules_in(findings)


def test_silent_except_accepts_logging_raise_or_narrow(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/state.py",
        """
        import logging

        logger = logging.getLogger(__name__)

        def a():
            try:
                return 1
            except Exception:
                logger.exception("boom")

        def b():
            try:
                return 1
            except Exception as e:
                raise RuntimeError("ctx") from e

        def c():
            try:
                return 1
            except OSError:
                pass  # narrow: not this rule's business
        """,
    )
    assert "silent-except" not in rules_in(findings)


def test_silent_except_only_applies_to_runtime_dirs(tmp_path):
    findings = lint_file(
        tmp_path,
        "rllib/algo.py",
        """
        def load():
            try:
                return 1
            except Exception:
                pass
        """,
    )
    assert "silent-except" not in rules_in(findings)


# --------------------------------------------------------------------- GL004

_PROTOCOL_OK = """
import enum


class MsgType(enum.IntEnum):
    REPLY = 0
    ERROR_REPLY = 1
    PING = 10
    PONG = 11
"""

_SERVER_OK = """
from proto import MsgType


class Server:
    async def h_ping(self, p):
        return {}

    _HANDLERS = {}


Server._HANDLERS = {
    MsgType.PING: Server.h_ping,
}


def dispatch(msg_type):
    if msg_type == MsgType.PONG:
        return "pong"
"""


def test_protocol_clean_fixture_passes(tmp_path):
    write(tmp_path, "proto.py", _PROTOCOL_OK)
    write(tmp_path, "server.py", _SERVER_OK)
    findings = lint_paths([str(tmp_path)])
    assert "protocol-exhaustive" not in rules_in(findings)


def test_protocol_flags_duplicate_values(tmp_path):
    write(
        tmp_path,
        "proto.py",
        _PROTOCOL_OK.replace("PONG = 11", "PONG = 10"),
    )
    write(tmp_path, "server.py", _SERVER_OK)
    findings = lint_paths([str(tmp_path)])
    msgs = [f.message for f in findings if f.rule_name == "protocol-exhaustive"]
    assert any("duplicates" in m for m in msgs)


def test_protocol_flags_unhandled_member(tmp_path):
    write(tmp_path, "proto.py", _PROTOCOL_OK + "    ORPHAN = 99\n")
    write(tmp_path, "server.py", _SERVER_OK)
    findings = lint_paths([str(tmp_path)])
    msgs = [f.message for f in findings if f.rule_name == "protocol-exhaustive"]
    assert any("ORPHAN" in m and "no receiving-side" in m for m in msgs)


def test_protocol_flags_undeclared_reference(tmp_path):
    write(tmp_path, "proto.py", _PROTOCOL_OK)
    write(
        tmp_path,
        "server.py",
        _SERVER_OK + "\n\ndef send():\n    return MsgType.MISSING\n",
    )
    findings = lint_paths([str(tmp_path)])
    msgs = [f.message for f in findings if f.rule_name == "protocol-exhaustive"]
    assert any("MISSING" in m and "not declared" in m for m in msgs)


def test_protocol_noop_without_enum(tmp_path):
    findings = lint_file(tmp_path, "anything.py", "X = 1\n")
    assert "protocol-exhaustive" not in rules_in(findings)


def test_protocol_handles_auto_members(tmp_path):
    # enum.auto() members are declared (no bogus "not declared" finding)
    # and participate in the duplicate check
    write(
        tmp_path,
        "proto.py",
        _PROTOCOL_OK.replace("PONG = 11", "PONG = enum.auto()"),
    )
    write(tmp_path, "server.py", _SERVER_OK)
    findings = [
        f for f in lint_paths([str(tmp_path)]) if f.rule_name == "protocol-exhaustive"
    ]
    assert findings == []
    # auto() after 10 yields 11; an explicit 11 after it must collide
    write(
        tmp_path,
        "proto.py",
        _PROTOCOL_OK.replace("PONG = 11", "PONG = enum.auto()") + "    CLASH = 11\n",
    )
    msgs = [
        f.message
        for f in lint_paths([str(tmp_path)])
        if f.rule_name == "protocol-exhaustive"
    ]
    assert any("CLASH" in m and "duplicates" in m for m in msgs)


def test_protocol_flags_bare_name_alias(tmp_path):
    write(tmp_path, "proto.py", _PROTOCOL_OK + "    PING_ALIAS = PING\n")
    write(tmp_path, "server.py", _SERVER_OK)
    msgs = [
        f.message
        for f in lint_paths([str(tmp_path)])
        if f.rule_name == "protocol-exhaustive"
    ]
    assert any("PING_ALIAS" in m and "duplicates" in m for m in msgs)


# --------------------------------------------------------------------- GL005

_THREADED_PREAMBLE = """
import threading

_CACHE = {}
_LOCK = threading.Lock()


def start():
    threading.Thread(target=lambda: None).start()
"""


def test_lock_discipline_flags_unguarded_mutation(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/state.py",
        _THREADED_PREAMBLE
        + """

def record(k, v):
    _CACHE[k] = v
""",
    )
    assert "lock-discipline" in rules_in(findings)


def test_lock_discipline_accepts_with_lock_and_locked_suffix(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/state.py",
        _THREADED_PREAMBLE
        + """

def record(k, v):
    with _LOCK:
        _CACHE[k] = v


def _record_locked(k, v):
    _CACHE[k] = v


async def record_async(k, v):
    async with _LOCK:
        _CACHE[k] = v
""",
    )
    assert "lock-discipline" not in rules_in(findings)


def test_lock_discipline_accepts_guarded_by_annotation(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/state.py",
        """
        import threading

        _CACHE = {}  # graftlint: guarded-by=_LOCK


        def start():
            threading.Thread(target=lambda: None).start()


        def record(k, v):
            _CACHE[k] = v
        """,
    )
    assert "lock-discipline" not in rules_in(findings)


def test_lock_discipline_covers_annotated_globals(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/state.py",
        """
        import threading
        from typing import Dict

        _CACHE: Dict[str, int] = {}


        def start():
            threading.Thread(target=lambda: None).start()


        def record(k, v):
            _CACHE[k] = v
        """,
    )
    assert "lock-discipline" in rules_in(findings)


def test_lock_discipline_silent_in_unthreaded_module(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/state.py",
        """
        _CACHE = {}


        def record(k, v):
            _CACHE[k] = v
        """,
    )
    assert "lock-discipline" not in rules_in(findings)


# --------------------------------------------------------------------- GL006


def test_resource_hygiene_flags_inline_and_unclosed(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/io_helpers.py",
        """
        import json


        def inline(p):
            return json.load(open(p))


        def unclosed(p):
            fh = open(p)
            return fh.read()
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "resource-hygiene") == 2


def test_resource_hygiene_accepts_with_close_return_and_self(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/io_helpers.py",
        """
        import socket


        def ctx(p):
            with open(p) as fh:
                return fh.read()


        def closed(p):
            fh = open(p)
            try:
                return fh.read()
            finally:
                fh.close()


        def transfer(p):
            fh = open(p)
            return fh


        class Holder:
            def attach(self, host):
                s = socket.create_connection((host, 80))
                self.sock = s
        """,
    )
    assert "resource-hygiene" not in rules_in(findings)


# --------------------------------------------------------------------- GL007


def test_no_assert_flags_server_asserts(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/srv.py",
        """
        def register(reply):
            assert reply.get("ok")
        """,
    )
    assert "no-assert-server" in rules_in(findings)


def test_no_assert_allows_explicit_raise_and_nonserver_dirs(tmp_path):
    ok = lint_file(
        tmp_path,
        "gcs/srv.py",
        """
        def register(reply):
            if not reply.get("ok"):
                raise RuntimeError("registration rejected")
        """,
    )
    assert "no-assert-server" not in rules_in(ok)
    elsewhere = lint_file(tmp_path, "rllib/algo.py", "def f(x):\n    assert x\n")
    assert "no-assert-server" not in rules_in(elsewhere)


# --------------------------------------------------------------------- GL008


def test_event_schema_flags_bad_severity_and_clock_field(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/events_use.py",
        """
        class S:
            def _record_event(self, severity, source, message, **fields):
                pass

            def go(self):
                self._record_event("FATAL", "node", "boom")
                self._record_event("INFO", "node", "ok", timestamp=1.0)
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "event-record-schema") == 2


def test_event_schema_flags_wire_payload_drift(tmp_path):
    findings = lint_file(
        tmp_path,
        "raylet/emit.py",
        """
        async def emit(conn, MsgType):
            await conn.send(
                MsgType.RECORD_EVENT,
                {
                    "severity": "NOTICE",
                    "source": "store",
                    "message": "m",
                    "fields": {"time": 1},
                },
            )
        """,
    )
    got = [f for f in findings if f.rule_name == "event-record-schema"]
    assert len(got) == 2  # bad severity + clock-drift field


def test_event_schema_accepts_canonical_records(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/events_use.py",
        """
        class S:
            def _record_event(self, severity, source, message, **fields):
                pass

            def go(self):
                self._record_event("WARNING", "object_store", "pressure", node_id="a")
        """,
    )
    assert "event-record-schema" not in rules_in(findings)


def test_event_schema_flags_noncanonical_phase_stamp(tmp_path):
    """Flight-recorder stamp sites must use the task_events.PHASES
    vocabulary — a typo'd phase drops out of every duration/histogram/
    timeline join silently."""
    findings = lint_file(
        tmp_path,
        "core/stamps.py",
        """
        import time

        def run(spec, ph):
            ph["worker_deque"] = time.time()      # typo'd phase
            spec.phases["dispached"] = time.time()  # typo'd phase
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "event-record-schema") == 2


def test_event_schema_flags_bad_stamp_call_and_accepts_canonical(tmp_path):
    findings = lint_file(
        tmp_path,
        "core/stamps.py",
        """
        import time
        from ray_tpu._private import task_events

        def run(spec, ph, other):
            task_events.stamp(ph, "not_a_phase")
            ph["worker_dequeue"] = time.time()
            ph["exec_start"] = ph["arg_fetch_end"] = time.time()
            spec.phases["head_enqueue"] = time.time()
            task_events.stamp(ph, "put_end")
            dyn = "computed"
            task_events.stamp(ph, dyn)   # non-literal: skipped
            other["anything"] = 1        # not a stamp dict: skipped
        """,
    )
    got = [f for f in findings if f.rule_name == "event-record-schema"]
    assert len(got) == 1 and "not_a_phase" in got[0].message


def test_event_schema_flags_span_outside_the_engine_vocabulary(tmp_path):
    """Engine-thread profiler spans are matched by name in trace readers:
    a literal span() name must come from task_events.ENGINE_SPANS."""
    findings = lint_file(
        tmp_path,
        "serve/engine/spans_use.py",
        """
        from ray_tpu.serve.tracing import span

        def turn(name):
            with span("engine/decode"):
                pass
            with span("engine/decod"):   # typo'd span
                pass
            with span(name):             # non-literal: skipped
                pass
        """,
    )
    got = [f for f in findings if f.rule_name == "event-record-schema"]
    assert len(got) == 1 and "engine/decod" in got[0].message


# --------------------------------------------------------------------- GL009


def test_mutable_default_flagged(tmp_path):
    findings = lint_file(
        tmp_path,
        "anywhere.py",
        """
        def f(x=[]):
            return x


        def g(*, y={}):
            return y
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "mutable-default") == 2


def test_mutable_default_allows_none_and_immutable(tmp_path):
    findings = lint_file(
        tmp_path,
        "anywhere.py",
        """
        def f(x=None, y=(), z="s", n=3):
            return x, y, z, n
        """,
    )
    assert "mutable-default" not in rules_in(findings)


# --------------------------------------------------------------------- GL010


def test_import_time_thread_flagged(tmp_path):
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading

        _t = threading.Thread(target=lambda: None, daemon=True)
        _t.start()
        """,
    )
    assert "import-time-thread" in rules_in(findings)


def test_import_time_thread_allows_main_guard_and_functions(tmp_path):
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading


        def start():
            threading.Thread(target=lambda: None).start()


        if __name__ == "__main__":
            threading.Thread(target=start).start()
        """,
    )
    assert "import-time-thread" not in rules_in(findings)


# --------------------------------------------------------------------- GL011


def test_anonymous_lock_flagged_in_witness_aware_module(tmp_path):
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading

        from ray_tpu.util.lockwitness import named_lock

        _named = named_lock("mod._named")
        _bare = threading.Lock()
        """,
    )
    assert "anonymous-lock" in rules_in(findings)
    assert len([f for f in findings if f.rule_name == "anonymous-lock"]) == 1


def test_anonymous_lock_covers_rlock_and_condition(tmp_path):
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading

        from ray_tpu.util.lockwitness import named_rlock

        _r = threading.RLock()
        _c = threading.Condition()
        """,
    )
    assert len([f for f in findings if f.rule_name == "anonymous-lock"]) == 2


def test_anonymous_lock_ignores_modules_without_lockwitness(tmp_path):
    """Importing lockwitness is the opt-in: plain modules keep plain
    locks without ceremony."""
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading

        _bare = threading.Lock()
        """,
    )
    assert "anonymous-lock" not in rules_in(findings)


def test_anonymous_lock_suppression(tmp_path):
    findings = lint_file(
        tmp_path,
        "mod.py",
        """
        import threading

        from ray_tpu.util.lockwitness import named_lock

        _bare = threading.Lock()  # graftlint: disable=anonymous-lock -- fixture: process-local scratch
        """,
    )
    assert "anonymous-lock" not in rules_in(findings)


# -------------------------------------------------------------- suppressions

_VIOLATION = """
def load():
    try:
        return 1
    except Exception:{trailing}
        pass
"""


def test_trailing_suppression(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/a.py",
        _VIOLATION.format(
            trailing="  # graftlint: disable=silent-except -- teardown"
        ),
    )
    assert "silent-except" not in rules_in(findings)


def test_standalone_suppression_covers_next_line(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/b.py",
        """
        def load():
            try:
                return 1
            # graftlint: disable=silent-except -- intentional
            except Exception:
                pass
        """,
    )
    assert "silent-except" not in rules_in(findings)


def test_file_level_suppression_and_all(tmp_path):
    by_rule = lint_file(
        tmp_path,
        "gcs/c.py",
        "# graftlint: disable-file=silent-except\n" + _VIOLATION.format(trailing=""),
    )
    assert "silent-except" not in rules_in(by_rule)
    by_all = lint_file(
        tmp_path,
        "gcs/d.py",
        _VIOLATION.format(trailing="  # graftlint: disable=all"),
    )
    assert "silent-except" not in rules_in(by_all)


def test_trailing_suppression_does_not_bleed_to_next_line(tmp_path):
    # a trailing disable on line N must not silently disable the rule on
    # line N+1 (regression: enum members under a suppressed member lost
    # their protocol-exhaustive protection)
    findings = lint_file(
        tmp_path,
        "gcs/bleed.py",
        """
        def first():
            try:
                return 1
            except Exception:  # graftlint: disable=silent-except -- ok here
                pass


        def second():
            try:
                return 1
            except Exception:
                pass
        """,
    )
    assert sum(1 for f in findings if f.rule_name == "silent-except") == 1


def test_scoped_rules_survive_single_file_invocation_from_any_cwd(tmp_path, monkeypatch):
    bad = write(tmp_path, "gcs/inner.py", _VIOLATION.format(trailing=""))
    monkeypatch.chdir(tmp_path / "gcs")
    findings = lint_paths([bad])
    assert "silent-except" in rules_in(findings)


def test_wrong_rule_suppression_does_not_apply(tmp_path):
    findings = lint_file(
        tmp_path,
        "gcs/e.py",
        _VIOLATION.format(trailing="  # graftlint: disable=mutable-default"),
    )
    assert "silent-except" in rules_in(findings)


# ----------------------------------------------------- select/ignore, errors


def test_select_and_ignore_filter_rules(tmp_path):
    write(tmp_path, "gcs/f.py", _VIOLATION.format(trailing="") + "\n\ndef g(x=[]):\n    return x\n")
    only_defaults = lint_paths([str(tmp_path)], select=["mutable-default"])
    assert rules_in(only_defaults) == {"mutable-default"}
    without_defaults = lint_paths([str(tmp_path)], ignore=["GL009"])
    assert "mutable-default" not in rules_in(without_defaults)


def test_syntax_error_is_a_finding(tmp_path):
    findings = lint_file(tmp_path, "broken.py", "def f(:\n")
    assert any(f.rule_name == "parse-error" for f in findings)


def test_missing_path_fails_closed(tmp_path):
    with pytest.raises(OSError):
        lint_paths([str(tmp_path / "no_such_dir")])
    assert graftlint_main([str(tmp_path / "no_such_dir")]) == 2


def test_unknown_select_token_is_a_usage_error(tmp_path):
    good = write(tmp_path, "ok.py", "X = 1\n")
    with pytest.raises(ValueError):
        lint_paths([good], select=["GL03"])  # typo for GL003
    assert graftlint_main(["--select", "GL03", good]) == 2
    assert graftlint_main(["--ignore", "not-a-rule", good]) == 2


# ------------------------------------------------------------------ reporters


def test_json_reporter_schema(tmp_path):
    write(tmp_path, "gcs/g.py", _VIOLATION.format(trailing=""))
    findings = lint_paths([str(tmp_path)])
    doc = json.loads(format_json(findings))
    assert doc["version"] == 1
    assert doc["tool"] == "graftlint"
    assert doc["total"] == len(findings) > 0
    assert doc["counts"]["silent-except"] >= 1
    for item in doc["findings"]:
        assert set(item) == {"file", "line", "col", "rule", "name", "message"}
        assert isinstance(item["line"], int) and item["line"] > 0
        assert item["rule"].startswith("GL")


def test_text_reporter_mentions_rule_and_location(tmp_path):
    write(tmp_path, "gcs/h.py", _VIOLATION.format(trailing=""))
    findings = lint_paths([str(tmp_path)])
    text = format_text(findings)
    assert "silent-except" in text and "gcs/h.py" in text
    assert format_text([]) == "graftlint: clean"
    assert "total" in format_text(findings, statistics=True)
    assert "total" not in format_text(findings, statistics=False)
    assert format_text([], statistics=True).startswith("graftlint: clean")


# ------------------------------------------------------------------------ CLI


def test_cli_exit_codes(tmp_path, capsys):
    bad = write(tmp_path, "gcs/i.py", _VIOLATION.format(trailing=""))
    assert graftlint_main([bad]) == 1
    good = write(tmp_path, "gcs/j.py", "def f():\n    return 1\n")
    assert graftlint_main([good]) == 0
    assert graftlint_main(["--list-rules"]) == 0
    capsys.readouterr()


def test_shipped_tree_is_clean():
    """Acceptance: `python -m ray_tpu.tools.graftlint ray_tpu/` exits 0."""
    findings = lint_paths([os.path.join(REPO_ROOT, "ray_tpu")])
    assert findings == [], format_text(findings)
