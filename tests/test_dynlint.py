"""dynlint: fixture-verified rule behavior + the tier-1 enforcement gate.

Each rule gets at least one true-positive and one true-negative fixture
(the acceptance contract for the analyzer), plus suppression, baseline,
and CLI exit-code coverage. The enforcement test at the bottom (marker:
``dynlint``) is the CI gate: the whole package must lint clean modulo
the committed baseline — a new violation in a PR fails tier-1 here.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dynamo_tpu.analysis import (  # noqa: E402
    all_rules,
    diff_against_baseline,
    get_rules,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)

PACKAGE_ROOT = os.path.join(REPO_ROOT, "dynamo_tpu")
BASELINE = os.path.join(REPO_ROOT, "scripts", "dynlint_baseline.json")


def findings(src, rule):
    return lint_source(textwrap.dedent(src), get_rules([rule]))


def rule_names(src, rule):
    return [f.rule for f in findings(src, rule)]


# --------------------------------------------------------------------------
# async-blocking
# --------------------------------------------------------------------------


def test_async_blocking_flags_sleep_in_async_def():
    out = findings(
        """
        import time
        async def work():
            time.sleep(1)
        """,
        "async-blocking",
    )
    assert len(out) == 1 and "time.sleep" in out[0].message
    assert out[0].line == 4


def test_async_blocking_resolves_from_imports_and_aliases():
    assert rule_names(
        """
        from time import sleep
        async def work():
            sleep(1)
        """,
        "async-blocking",
    ) == ["async-blocking"]
    assert rule_names(
        """
        import subprocess as sp
        async def work():
            sp.run(["ls"])
        """,
        "async-blocking",
    ) == ["async-blocking"]


def test_async_blocking_flags_open_and_requests():
    src = """
    import requests
    async def fetch(path):
        f = open(path)
        return requests.get("http://x")
    """
    assert len(findings(src, "async-blocking")) == 2


def test_async_blocking_ignores_locals_named_like_modules():
    # a mapping of in-flight requests is a natural name in this codebase;
    # attribute chains only resolve when the root is actually imported
    assert not findings(
        """
        async def lookup(requests, rid):
            return requests.get(rid)
        async def resolve(socket):
            return socket.getaddrinfo()
        """,
        "async-blocking",
    )


def test_async_blocking_ignores_sync_defs_and_async_sleep():
    assert not findings(
        """
        import time, asyncio
        def sync_work():
            time.sleep(1)
        async def ok():
            await asyncio.sleep(1)
        """,
        "async-blocking",
    )


def test_async_blocking_skips_nested_sync_def():
    # the nested def runs wherever it's called (typically an executor);
    # flagging it here would force suppressions on the executor idiom
    assert not findings(
        """
        import time
        async def work(loop):
            def blocking():
                time.sleep(1)
            await loop.run_in_executor(None, blocking)
        """,
        "async-blocking",
    )


# --------------------------------------------------------------------------
# task-leak
# --------------------------------------------------------------------------


def test_task_leak_flags_discarded_handle():
    out = findings(
        """
        import asyncio
        async def go(coro):
            asyncio.create_task(coro)
        """,
        "task-leak",
    )
    assert len(out) == 1 and "discarded" in out[0].message


def test_task_leak_flags_discarded_ensure_future_and_loop_spawn():
    src = """
    import asyncio
    async def go(loop, coro):
        asyncio.ensure_future(coro)
        loop.create_task(coro)
    """
    assert len(findings(src, "task-leak")) == 2


def test_task_leak_ignores_kept_handles():
    assert not findings(
        """
        import asyncio
        async def go(self, coro, tasks):
            t = asyncio.create_task(coro)
            self._task = asyncio.create_task(coro)
            tasks["x"] = asyncio.create_task(coro)
            await asyncio.create_task(coro)
            return t
        """,
        "task-leak",
    )


def test_task_leak_ignores_task_groups():
    assert not findings(
        """
        import asyncio
        async def go(coro):
            async with asyncio.TaskGroup() as tg:
                tg.create_task(coro)
        """,
        "task-leak",
    )


# --------------------------------------------------------------------------
# lock-across-await
# --------------------------------------------------------------------------


def test_lock_flags_threading_lock_in_async_def():
    out = findings(
        """
        import threading
        async def work():
            lock = threading.Lock()
        """,
        "lock-across-await",
    )
    assert len(out) == 1 and "threading.Lock" in out[0].message


def test_lock_flags_lock_held_across_await():
    out = findings(
        """
        async def work(self, thing):
            with self._lock:
                await thing()
        """,
        "lock-across-await",
    )
    assert len(out) == 1 and "across an await" in out[0].message


def test_lock_ignores_asyncio_lock_and_sync_contexts():
    assert not findings(
        """
        import asyncio, threading
        def sync_work():
            lock = threading.Lock()
            with lock:
                pass
        async def ok(self):
            self._lock = asyncio.Lock()
            async with self._lock:
                await asyncio.sleep(0)
        """,
        "lock-across-await",
    )


def test_lock_ignores_non_lock_context_managers_with_await():
    assert not findings(
        """
        async def work(self, session):
            with self.tracer.span("x"):
                await session.send()
        """,
        "lock-across-await",
    )


# --------------------------------------------------------------------------
# jit-impure
# --------------------------------------------------------------------------


def test_jit_impure_flags_print_and_self_mutation_in_decorated_fn():
    src = """
    import jax
    class M:
        @jax.jit
        def step(self, x):
            print("tracing", x)
            self.calls = self.calls + 1
            return x
    """
    msgs = [f.message for f in findings(src, "jit-impure")]
    assert len(msgs) == 2
    assert any("print()" in m for m in msgs)
    assert any("mutates self.calls" in m for m in msgs)


def test_jit_impure_flags_host_sync_in_jit_call_form():
    # the call form jax.jit(fn) is how model_runner builds every step
    src = """
    import jax
    import numpy as np
    def step(x):
        return np.asarray(x).item()
    compiled = jax.jit(step, donate_argnums=(0,))
    """
    msgs = [f.message for f in findings(src, "jit-impure")]
    assert any("numpy.asarray" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


def test_jit_impure_flags_global_mutation_and_partial_decorator():
    src = """
    import functools, jax
    COUNT = 0
    @functools.partial(jax.jit, static_argnums=(1,))
    def step(x, n):
        global COUNT
        COUNT = COUNT + 1
        return x
    """
    out = findings(src, "jit-impure")
    assert len(out) == 1 and "global 'COUNT'" in out[0].message


def test_jit_impure_ignores_untraced_code_and_debug_print():
    assert not findings(
        """
        import jax
        import numpy as np
        def plain(x):
            print(x)          # not traced: fine
            return np.asarray(x).item()
        @jax.jit
        def traced(x):
            jax.debug.print("x={}", x)   # the traced print: fine
            return x * 2
        """,
        "jit-impure",
    )


# --------------------------------------------------------------------------
# silent-except
# --------------------------------------------------------------------------


def test_silent_except_flags_swallowed_broad_handlers():
    src = """
    def f():
        try:
            work()
        except Exception:
            pass
    def g():
        try:
            work()
        except:
            return None
    """
    assert len(findings(src, "silent-except")) == 2


def test_silent_except_ignores_logged_raised_and_narrow():
    assert not findings(
        """
        import logging
        logger = logging.getLogger(__name__)
        def f():
            try:
                work()
            except Exception:
                logger.exception("work failed")
        def g():
            try:
                work()
            except Exception as e:
                raise RuntimeError("ctx") from e
        def h():
            try:
                work()
            except ConnectionResetError:
                pass   # narrow: presumed deliberate
        """,
        "silent-except",
    )


def test_silent_except_treats_future_set_exception_as_observed():
    # disagg/transfer.py's daemon-thread bridge: the error propagates
    # through the Future, which is observation, not swallowing
    assert not findings(
        """
        def work(fut, fn):
            try:
                fut.set_result(fn())
            except BaseException as e:
                fut.set_exception(e)
        """,
        "silent-except",
    )


# --------------------------------------------------------------------------
# metric-name
# --------------------------------------------------------------------------


def test_metric_name_flags_off_convention_registration():
    src = """
    def register(reg):
        reg.counter("dynamo_scheduler_preemptions", "help")
        reg.histogram("dynamo_kv_usage_ratio", "help")
    """
    out = findings(src, "metric-name")
    # the counter name breaks two clauses (unit suffix + _total), the
    # ratio histogram one — each clause is its own finding
    assert len(out) == 3
    assert any("_total" in f.message for f in out)
    assert any("base unit" in f.message for f in out)


def test_metric_name_unit_suffix_requires_segment_boundary():
    # "subtotal"/"kilobytes" merely END in a unit string; the unit must
    # be the whole last segment
    src = """
    def register(reg):
        reg.gauge("dynamo_scheduler_subtotal", "help")
        reg.histogram("dynamo_transfer_kilobytes", "help")
    """
    out = findings(src, "metric-name")
    assert len(out) >= 2
    assert {f.line for f in out} == {3, 4}


def test_metric_name_accepts_conforming_registration():
    assert not findings(
        """
        def register(reg):
            reg.counter("dynamo_scheduler_preemptions_total", "help")
            reg.histogram("dynamo_scheduler_step_duration_seconds", "help")
            reg.gauge("dynamo_kv_block_usage_ratio", "help")
        """,
        "metric-name",
    )


# --------------------------------------------------------------------------
# suppressions
# --------------------------------------------------------------------------


def test_suppression_on_same_line_and_line_above():
    src = """
    import time
    async def work():
        time.sleep(1)  # dynlint: allow(async-blocking) - test latency injection
        # dynlint: allow(async-blocking) - second form
        time.sleep(2)
        time.sleep(3)
    """
    out = findings(src, "async-blocking")
    assert len(out) == 1 and out[0].line == 7


def test_suppression_is_per_rule():
    # an allow() for a DIFFERENT rule must not mask this one
    src = """
    import time
    async def work():
        time.sleep(1)  # dynlint: allow(silent-except) - wrong rule
    """
    assert len(findings(src, "async-blocking")) == 1


def test_trailing_suppression_does_not_bleed_to_next_line():
    # an allow on a line of CODE covers that line only; a later edit
    # adding the same violation right below must still be flagged
    src = """
    import time
    async def work():
        time.sleep(1)  # dynlint: allow(async-blocking) - justified here
        time.sleep(2)
    """
    out = findings(src, "async-blocking")
    assert len(out) == 1 and out[0].line == 5


def test_suppression_allows_multiple_rules_and_all():
    src = """
    import time
    async def work():
        time.sleep(1)  # dynlint: allow(async-blocking, task-leak) - multi
        time.sleep(2)  # dynlint: allow(all) - blanket
    """
    assert not findings(src, "async-blocking")


# --------------------------------------------------------------------------
# baseline mechanics
# --------------------------------------------------------------------------


def test_baseline_roundtrip_and_new_violation_detection(tmp_path):
    src_v1 = textwrap.dedent(
        """
        import time
        async def a():
            time.sleep(1)
        """
    )
    rules = get_rules(["async-blocking"])
    first = lint_source(src_v1, rules, rel="pkg/mod.py")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, first)
    baseline = load_baseline(path)

    # same findings (even at shifted lines) -> clean
    shifted = lint_source("# moved\n# down\n" + src_v1, rules, rel="pkg/mod.py")
    diff = diff_against_baseline(shifted, baseline)
    assert not diff.new and len(diff.known) == 1

    # one MORE identical violation -> exactly the excess is new
    src_v2 = src_v1 + "    time.sleep(1)\n"
    diff = diff_against_baseline(
        lint_source(src_v2, rules, rel="pkg/mod.py"), baseline
    )
    assert len(diff.new) == 1 and len(diff.known) == 1

    # violation fixed -> stale entry reported, nothing fails
    diff = diff_against_baseline([], baseline)
    assert not diff.new and diff.stale


def test_baseline_partial_fix_is_stale_not_free_allowance():
    """Fixing one of N identical debt items must surface as stale, or
    the freed count would silently absorb a future new violation."""
    rules = get_rules(["async-blocking"])
    two = lint_source(
        textwrap.dedent(
            """
            import time
            async def a():
                time.sleep(1)
                time.sleep(1)
            """
        ),
        rules, rel="pkg/mod.py",
    )
    baseline = {two[0].key(): 2}
    one = lint_source(
        textwrap.dedent(
            """
            import time
            async def a():
                time.sleep(1)
            """
        ),
        rules, rel="pkg/mod.py",
    )
    diff = diff_against_baseline(one, baseline)
    assert not diff.new and len(diff.known) == 1
    assert diff.stale == [two[0].key()]


# --------------------------------------------------------------------------
# CLI contract
# --------------------------------------------------------------------------


def _write_pkg(tmp_path, body):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(body))
    return str(pkg)


def test_cli_exit_codes_and_update_baseline(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)
    pkg = _write_pkg(
        tmp_path,
        """
        import time
        async def a():
            time.sleep(1)
        """,
    )
    baseline = str(tmp_path / "b.json")
    # dirty, no baseline -> 1
    assert dynlint.main(["dynlint", pkg, "--baseline", baseline]) == 1
    # record the debt -> 0 afterwards
    assert dynlint.main(
        ["dynlint", pkg, "--baseline", baseline, "--update-baseline"]) == 0
    assert dynlint.main(["dynlint", pkg, "--baseline", baseline]) == 0
    # --no-baseline still reports it
    assert dynlint.main(
        ["dynlint", pkg, "--baseline", baseline, "--no-baseline"]) == 1
    # unknown rule -> usage error
    assert dynlint.main(["dynlint", pkg, "--rules", "nope"]) == 2
    entries = json.load(open(baseline))["entries"]
    assert len(entries) == 1 and "async-blocking" in next(iter(entries))


def test_cli_refuses_scoped_update_of_shared_baseline(tmp_path):
    """--update-baseline with --rules or a narrowed path would rewrite
    the SHARED baseline from partial findings, deleting out-of-scope
    entries — the CLI must refuse (exit 2) before writing."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)
    before = open(BASELINE).read()
    assert dynlint.main(
        ["dynlint", "--rules", "silent-except", "--update-baseline"]) == 2
    assert dynlint.main(
        ["dynlint", os.path.join(PACKAGE_ROOT, "engine"),
         "--update-baseline"]) == 2
    assert open(BASELINE).read() == before, "shared baseline was rewritten"
    # a scoped update pointed at a PRIVATE baseline file is fine
    private = str(tmp_path / "scoped.json")
    assert dynlint.main(
        ["dynlint", os.path.join(PACKAGE_ROOT, "engine"),
         "--baseline", private, "--update-baseline"]) == 0
    assert os.path.exists(private)


def test_check_metric_names_script_contract_unchanged():
    """The shim keeps the historical CLI: exit 0 + conformance summary."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "check_metric_names.py")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "conform" in proc.stdout


# --------------------------------------------------------------------------
# enforcement: the package itself is clean modulo the committed baseline
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_dynamo_tpu_lints_clean_modulo_baseline():
    findings_all = lint_paths([PACKAGE_ROOT], all_rules())
    diff = diff_against_baseline(findings_all, load_baseline(BASELINE))
    assert not diff.new, "new dynlint violations:\n" + "\n".join(
        f.render() for f in diff.new
    )
    assert not diff.stale, (
        "stale baseline entries (fixed debt — run "
        "'python scripts/dynlint.py --update-baseline' to prune):\n"
        + "\n".join(diff.stale)
    )


def test_kernel_campaign_ops_modules_are_jit_impure_clean():
    """The kernel-campaign modules — the SP paged prefix walk and the
    decode kernels it shares helpers with — must carry ZERO jit-impure
    findings, with no baseline
    allowance: host-effect Python inside these traced bodies would fire
    once per Mosaic specialization compile and skew every differential."""
    mods = [
        os.path.join(PACKAGE_ROOT, "ops", "pallas_sp.py"),
        os.path.join(PACKAGE_ROOT, "ops", "pallas_decode.py"),
    ]
    found = lint_paths(mods, get_rules(["jit-impure"]))
    assert not found, "\n".join(f.render() for f in found)


def test_overlapping_paths_do_not_double_count():
    """dynlint dynamo_tpu dynamo_tpu/engine must not lint guided.py twice
    — duplicate counts would trip the baseline ratchet with phantoms."""
    engine = os.path.join(PACKAGE_ROOT, "engine")
    once = lint_paths([engine], get_rules(["silent-except"]))
    twice = lint_paths([engine, os.path.join(engine, "guided.py")],
                       get_rules(["silent-except"]))
    assert [f.key() for f in once] == [f.key() for f in twice]


def test_lint_paths_raises_on_missing_path():
    """A typo'd scope must never read as a clean scan."""
    with pytest.raises(FileNotFoundError):
        lint_paths([os.path.join(REPO_ROOT, "no_such_dir")], all_rules())
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)
    assert dynlint.main(["dynlint", "no_such_dir"]) == 2


def test_scoped_paths_produce_baseline_stable_keys():
    """dynlint <repo>, <file> and <subdir> must key findings identically
    to the package-wide scan, or the baseline only works for full runs."""
    guided = os.path.join(PACKAGE_ROOT, "engine", "guided.py")
    for scope in (guided, os.path.join(PACKAGE_ROOT, "engine"), REPO_ROOT):
        found = lint_paths([scope], get_rules(["silent-except"]))
        files = {f.file for f in found}
        assert "dynamo_tpu/engine/guided.py" in files, (scope, files)
        diff = diff_against_baseline(found, load_baseline(BASELINE))
        assert not [f for f in diff.new
                    if f.file == "dynamo_tpu/engine/guided.py"]


# --------------------------------------------------------------------------
# the decode chain: the hot loop's purity contract
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_decode_pipeline_modules_pass_jit_impure_and_async_blocking():
    """The chained decode path lives or dies on two properties dynlint
    polices: no host syncs inside the traced burst program (jit-impure)
    and no blocking calls on the scheduler's event loop (async-blocking
    — the executor-side token sync must be the only host sync in the
    loop). Pin them with ZERO findings, not baseline-covered ones."""
    modules = [
        os.path.join(PACKAGE_ROOT, "engine", "scheduler.py"),
        os.path.join(PACKAGE_ROOT, "engine", "model_runner.py"),
        os.path.join(PACKAGE_ROOT, "engine", "block_allocator.py"),
    ]
    found = lint_paths(modules, get_rules(["jit-impure", "async-blocking"]))
    assert found == [], "pipeline hot path regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_scheduler_token_sync_is_the_only_loop_host_sync():
    """Structural pin for the pipeline's purity claim: inside
    engine/scheduler.py's async functions, every ``np.asarray`` host
    sync happens inside a nested (executor-bound) ``def``, never
    directly on the event loop."""
    import ast

    path = os.path.join(PACKAGE_ROOT, "engine", "scheduler.py")
    with open(path) as f:
        tree = ast.parse(f.read())

    def direct_calls(fn):
        """Call nodes in fn's body, excluding nested function bodies
        (those run wherever they're called — here, the executor)."""
        out = []
        stack = [n for n in ast.iter_child_nodes(fn)]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not fn:
                continue
            if isinstance(node, ast.Call):
                out.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return out

    offenders = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.AsyncFunctionDef):
            continue
        for call in direct_calls(node):
            f_ = call.func
            if (isinstance(f_, ast.Attribute) and f_.attr == "asarray"
                    and isinstance(f_.value, ast.Name)
                    and f_.value.id == "np"):
                offenders.append((node.name, call.lineno))
    assert not offenders, (
        "np.asarray on the scheduler event loop (host sync must ride "
        f"run_in_executor): {offenders}"
    )


def test_jit_impure_flags_host_sync_in_burst_shaped_program():
    """TP fixture shaped like the burst program: an np.asarray of the
    carry inside the traced function is exactly the per-dispatch stall
    the pipeline exists to remove — jit-impure must catch it."""
    out = findings(
        """
        import jax
        import numpy as np

        def build(step):
            def burst(carry, tokens0):
                toks = step(carry, tokens0)
                host = np.asarray(toks)   # host sync under trace
                return toks, host
            return jax.jit(burst)
        """,
        "jit-impure",
    )
    assert [f.rule for f in out] == ["jit-impure"]
    assert "numpy.asarray" in out[0].message


def test_async_blocking_flags_sync_sleep_in_pipelined_loop_shape():
    """TP fixture shaped like a naive dispatch-ahead loop that waits for
    the device with a blocking sleep on the event loop."""
    out = findings(
        """
        import time
        async def decode_pipelined(runner, bursts):
            for burst in bursts:
                runner.dispatch(burst)
                time.sleep(0.001)  # "wait for the device"
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


def test_async_blocking_flags_drain_callback_waiting_on_loop():
    """TP fixture shaped like a careless chained-decode drain: the
    callback reconciling a queued burst waits out the device with a
    blocking sleep ON the scheduler loop instead of syncing through the
    executor — exactly the hop the persistent loop's async row drain
    must ride (scheduler._apply_burst's run_in_executor)."""
    out = findings(
        """
        import time
        async def drain_chain(chain, apply_tokens):
            while chain:
                burst = chain.popleft()
                while not burst.toks.is_ready():
                    time.sleep(0.0005)  # "wait for the burst"
                apply_tokens(burst)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


# --------------------------------------------------------------------------
# streamed remote prefill: the transfer pipeline's purity contract
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_disagg_stream_modules_pass_jit_impure_and_async_blocking():
    """The streamed remote-prefill pipeline has the same two load-bearing
    properties as the decode pipeline: no host syncs under trace and no
    blocking work on the worker's event loop — the device gather is
    dispatch-only on the loop (it must serialize with the step's donated
    cache buffers) while every host sync (device→host frame copy, byte
    packing) and frame write rides the executor-bound pump. Pin the whole
    disagg vertical clean, ZERO findings (not baseline-covered ones)."""
    modules = [
        os.path.join(PACKAGE_ROOT, "disagg", "prefill_worker.py"),
        os.path.join(PACKAGE_ROOT, "disagg", "transfer.py"),
        os.path.join(PACKAGE_ROOT, "disagg", "ici_transfer.py"),
        os.path.join(PACKAGE_ROOT, "disagg", "coordinator.py"),
    ]
    found = lint_paths(modules, get_rules(["jit-impure", "async-blocking"]))
    assert found == [], "streamed transfer hot path regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_sync_wait_in_streaming_pump_shape():
    """TP fixture shaped like a naive frame pump that waits out the wire
    with a blocking sleep on the loop — exactly what the executor-bound
    pump discipline forbids."""
    out = findings(
        """
        import time
        async def frame_pump(frames, sock):
            for k, v in frames:
                sock.write(k.tobytes())
                time.sleep(0.01)  # "let the bytes drain"
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


# --------------------------------------------------------------------------
# flight recorder + stall watchdog: the always-on observability contract
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_flight_watchdog_modules_pass_async_blocking_and_task_leak():
    """The flight ring runs on EVERY hot path and the watchdog watches
    the loop it runs on, so their own discipline is load-bearing: the
    ring append must never touch the event loop (no blocking IO in async
    code — artifact writes ride run_in_executor) and the watchdog task
    must be held and cancelled on stop (a leaked watchdog would sample a
    dead engine forever). Pin both modules ZERO-finding, not
    baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "telemetry", "flight.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "watchdog.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "flight/watchdog discipline regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_task_leak_flags_watchdog_shaped_discarded_task():
    """TP fixture shaped like a careless watchdog: the sampling task's
    handle is dropped, so stop() can never cancel it and it samples a
    dead engine forever."""
    out = findings(
        """
        import asyncio

        class Watchdog:
            def start(self):
                asyncio.get_running_loop().create_task(self._run())

            async def _run(self):
                while True:
                    await asyncio.sleep(1.0)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


def test_async_blocking_flags_artifact_write_on_loop_shape():
    """TP fixture shaped like a naive trip handler that writes the
    flight artifact directly on the event loop — exactly the stall the
    watchdog exists to detect, committed by the watchdog itself."""
    out = findings(
        """
        import json

        async def on_trip(artifact, path):
            with open(path, "w") as f:
                json.dump(artifact, f)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]
    assert "open" in out[0].message


def test_jit_impure_flags_host_sync_in_gather_shaped_program():
    """TP fixture shaped like the frame gather: an np.asarray inside the
    traced gather is a per-frame device→host stall — the transfer would
    serialize against compute instead of overlapping it."""
    out = findings(
        """
        import jax
        import numpy as np

        def build(cache):
            def gather(ids):
                blocks = cache[:, ids]
                return np.asarray(blocks)   # host sync under trace
            return jax.jit(gather)
        """,
        "jit-impure",
    )
    assert [f.rule for f in out] == ["jit-impure"]
    assert "numpy.asarray" in out[0].message


@pytest.mark.dynlint
def test_enforcement_scan_is_not_vacuous():
    """The walk must actually see the tree: recorded debt is present and
    the analyzer parses every module (no parse-error findings)."""
    findings_all = lint_paths([PACKAGE_ROOT], all_rules())
    assert not [f for f in findings_all if f.rule == "parse-error"]
    # the committed baseline's debt is real, live findings
    diff = diff_against_baseline(findings_all, load_baseline(BASELINE))
    assert len(diff.known) >= 1


# --------------------------------------------------------------------------
# closed-loop SLA planner: the control loop's own discipline
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_planner_modules_pass_async_blocking_and_task_leak():
    """The planner loop is exactly the shape these rules police: a
    periodic asyncio task that calls out to cluster clients (kubectl
    subprocess, api-store REST — both MUST ride an executor) and that
    stop() must be able to cancel (a leaked planner keeps scaling a
    deployment nobody is watching). Pin the whole subsystem ZERO-finding,
    not baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "planner", "planner.py"),
        os.path.join(PACKAGE_ROOT, "planner", "policy.py"),
        os.path.join(PACKAGE_ROOT, "planner", "signals.py"),
        os.path.join(PACKAGE_ROOT, "planner", "admission.py"),
        os.path.join(PACKAGE_ROOT, "planner", "actuation.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "planner loop discipline regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_kubectl_on_loop_shape():
    """TP fixture shaped like a careless KubeActuator: the reconcile
    (a kubectl subprocess under the hood) runs directly on the planner's
    event loop, stalling every admission decision behind the API server."""
    out = findings(
        """
        import subprocess

        async def apply_scale(manifest):
            subprocess.run(["kubectl", "apply", "-f", "-"], input=manifest)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


def test_task_leak_flags_planner_shaped_discarded_loop():
    """TP fixture shaped like a careless planner: the observe→decide→
    actuate task handle is dropped, so stop() can never cancel it and it
    keeps patching replicas after shutdown."""
    out = findings(
        """
        import asyncio

        class Planner:
            def start(self):
                asyncio.create_task(self._loop())

            async def _loop(self):
                while True:
                    await asyncio.sleep(2.0)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


# --------------------------------------------------------------------------
# self-healing recovery: the drain/migrate/respawn stack's discipline
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_recovery_modules_pass_async_blocking_and_task_leak():
    """The recovery ladder runs precisely when the engine is ailing —
    a controller that blocks the event loop (a sleep-based respawn
    backoff, an inline KV gather) would wedge the very loop the watchdog
    is trying to save, and a dropped relay task would strand a migrated
    client stream. Pin the subsystem (and the fault-injection helper the
    chaos paths call from hot loops) ZERO-finding, not baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "recovery", "controller.py"),
        os.path.join(PACKAGE_ROOT, "recovery", "migration.py"),
        os.path.join(PACKAGE_ROOT, "utils", "faults.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "recovery discipline regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_respawn_loop_sleeping_on_loop():
    """TP fixture shaped like a careless respawn ladder: the exponential
    backoff runs time.sleep on the event loop, so every admission
    decision, watchdog sample, and relay frame stalls behind it."""
    out = findings(
        """
        import time

        async def respawn_with_backoff(spawn):
            delay = 1.0
            for _ in range(3):
                try:
                    return await spawn()
                except Exception:
                    time.sleep(delay)
                    delay *= 2
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


def test_task_leak_flags_migration_relay_shaped_discarded_task():
    """TP fixture shaped like a careless migrator: the relay task that
    forwards the peer's resumed stream is dropped on the floor — close()
    can never cancel it and its exception is silently lost along with
    the client's stream tail."""
    out = findings(
        """
        import asyncio

        class Migrator:
            def ship(self, er):
                asyncio.create_task(self._relay(er))

            async def _relay(self, er):
                while True:
                    await asyncio.sleep(0.1)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


# --------------------------------------------------------------------------
# request X-ray: the cross-process trace/SLO/device-time modules
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_xray_telemetry_modules_pass_async_blocking_and_task_leak():
    """The X-ray modules sit on every request's exit path (trace record,
    SLO verdict) and on the scheduler's reconciliation seams (device
    time), so their own discipline is load-bearing: span folding and SLO
    accounting are pure arithmetic that must never block the event loop,
    and nothing here may spawn an unheld task. Pin the whole vertical
    ZERO-finding, not baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "telemetry", "tracing.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "stitch.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "device_time.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "slo.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "x-ray telemetry discipline regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_span_export_write_on_loop_shape():
    """TP fixture shaped like a careless span exporter: serializing the
    stitched trace to disk directly on the event loop — exactly the
    stall the trace JSONL sink's writer thread (and the flight
    artifact's run_in_executor write) exist to avoid."""
    out = findings(
        """
        import json

        async def export_stitched_trace(trace, path):
            with open(path, "w") as f:
                json.dump(trace, f)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]
    assert "open" in out[0].message


# --------------------------------------------------------------------------
# fleet hub + incident recorder: the modules that run WHILE things break
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_fleet_observability_modules_pass_async_blocking_and_task_leak():
    """The hub's scrape loop shares the frontend's event loop and the
    incident recorder runs at the exact moment the process is already
    ailing — a bundle write or profiler capture on the loop would extend
    the very stall it is documenting, and a dropped capture/scrape task
    would silently lose the evidence. Pin all three modules ZERO-finding,
    not baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "telemetry", "hub.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "history.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "incidents.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "fleet observability discipline regressed:\n" + \
        "\n".join(f.render() for f in found)


def test_async_blocking_flags_bundle_write_on_loop_shape():
    """TP fixture shaped like a careless incident capture: serializing
    the bundle to disk directly on the event loop, right when the
    watchdog just reported that loop as the problem."""
    out = findings(
        """
        import json

        async def capture_bundle(manifest, artifact, path):
            with open(path, "w") as f:
                json.dump({"manifest": manifest, "flight": artifact}, f)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]
    assert "open" in out[0].message


def test_async_blocking_flags_profiler_capture_sleeping_on_loop():
    """TP fixture shaped like a careless incident profile window: the
    jax.profiler capture holds the trace open with time.sleep ON the
    loop — utils/profiling.capture_trace is executor-only for a reason."""
    out = findings(
        """
        import time

        async def profile_window(trace, seconds):
            with trace:
                time.sleep(seconds)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


def test_task_leak_flags_discarded_capture_task_shape():
    """TP fixture shaped like a careless trigger: the capture task is
    dropped on the floor — stop() can never await it and a failing
    capture's exception (the evidence loss!) is silently swallowed."""
    out = findings(
        """
        import asyncio

        class Recorder:
            def trigger(self, reason):
                asyncio.get_running_loop().create_task(self._capture(reason))

            async def _capture(self, reason):
                await asyncio.sleep(1.0)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


# --------------------------------------------------------------------------
# cluster KV fabric: spill I/O must ride the executor
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_kv_fabric_modules_pass_async_blocking_and_task_leak():
    """The fabric's pull pump shares the scheduler's event loop and the
    cold tier's spill writes fire from the host tier's drain (also
    loop-side): a blocking file read/write or a dropped spill future
    there stalls decode for every request. Pin both modules with ZERO
    findings (not baseline-covered ones) on the two rules that police
    exactly that — all disk I/O rides the executor with its future
    held (kv/cold_tier.py offer/close discipline)."""
    modules = [
        os.path.join(PACKAGE_ROOT, "kv", "fabric.py"),
        os.path.join(PACKAGE_ROOT, "kv", "cold_tier.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "KV fabric hot path regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_cold_spill_write_on_loop():
    """TP fixture shaped like the tempting-but-wrong cold-tier spill:
    writing the block file synchronously inside the async eviction hook
    blocks the scheduler loop for a disk round-trip per evicted block."""
    out = findings(
        """
        import os

        async def on_evict(path, sequence_hash, payload):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(payload)
            os.replace(tmp, path)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]
    assert "open" in out[0].message


# --------------------------------------------------------------------------
# multi-model registry plane: watch/pool loops share the serving loop
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_registry_modules_pass_async_blocking_and_task_leak():
    """The registry's pool-policy loop, cold-start tasks, and quota
    buckets all run ON the frontend's serving loop (single-loop
    discipline like the admission controller): a blocking call stalls
    every request, and a dropped cold-start or policy-loop task is a
    spawn nobody can cancel or observe failing. Pin the whole package
    with ZERO findings (not baseline-covered ones) on the two rules
    that police exactly that."""
    modules = [
        os.path.join(PACKAGE_ROOT, "registry", "cards.py"),
        os.path.join(PACKAGE_ROOT, "registry", "registry.py"),
        os.path.join(PACKAGE_ROOT, "registry", "pools.py"),
        os.path.join(PACKAGE_ROOT, "registry", "policy.py"),
        os.path.join(PACKAGE_ROOT, "registry", "tenants.py"),
    ]
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "registry plane regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_task_leak_flags_discarded_registry_watch_task():
    """TP fixture shaped like the tempting-but-wrong registry watcher:
    spawning the watch loop without holding the task means a worker
    churn event after GC silently stops rebinding routes — models keep
    serving stale pools and nobody sees the exception."""
    out = findings(
        """
        import asyncio

        class RegistryWatcher:
            async def start(self, watcher):
                asyncio.create_task(self._watch_loop(watcher))

            async def _watch_loop(self, watcher):
                async for ev in watcher:
                    self.apply(ev)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


# --------------------------------------------------------------------------
# unrestricted persistent decode (ISSUE 13): the in-carry spec/guided/
# stop-string machinery's purity contract
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_unrestricted_chain_modules_pass_jit_impure_and_async_blocking():
    """The reworked sampling module (suffix ring + stop hashes inside
    the traced burst) and the guided device-table builder must stay
    clean on the two rules that police the chain's purity: no host
    syncs under trace (jit-impure) and no blocking work on the event
    loop (async-blocking — the table compile's per-state vocab sweep
    rides an executor; scheduler._guided_chain_reason). Pin ZERO
    findings, not baseline-covered ones."""
    modules = [
        os.path.join(PACKAGE_ROOT, "engine", "sampling.py"),
        os.path.join(PACKAGE_ROOT, "engine", "guided.py"),
    ]
    found = lint_paths(modules, get_rules(["jit-impure", "async-blocking"]))
    assert found == [], "unrestricted-chain module regressed:\n" + "\n".join(
        f.render() for f in found
    )


def test_async_blocking_flags_grammar_table_compile_on_loop_shape():
    """TP fixture shaped like a careless guided-chain admission: the
    grammar's device-table compile busy-polls (and reads the piece
    table) ON the scheduler loop instead of riding an executor — the
    per-state vocab sweep is seconds of CPU for a real tokenizer, which
    would starve every live stream's drain."""
    out = findings(
        """
        import time
        async def admit_guided(sched, er, compile_table):
            table = compile_table(er.guided)   # O(states x vocab) sweep
            while table is None:
                time.sleep(0.01)               # "wait for the compile"
                table = compile_table(er.guided)
            sched.install_table(er, table)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


@pytest.mark.dynlint
def test_sp_prefill_modules_pass_jit_impure_and_async_blocking():
    """The sequence-parallel prefill seam (docs/long_context.md): the
    SP chunk ladder dispatches on the scheduler loop and must stay
    dispatch-only (no host syncs outside the executor), and the
    parallel attention modules trace under jit (no impurity). Pin the
    whole vertical ZERO-finding, not baseline-covered."""
    modules = [
        os.path.join(PACKAGE_ROOT, "parallel", "sequence.py"),
        os.path.join(PACKAGE_ROOT, "parallel", "ring_attention.py"),
        os.path.join(PACKAGE_ROOT, "llm", "embeddings.py"),
        os.path.join(PACKAGE_ROOT, "engine", "scheduler.py"),
    ]
    found = lint_paths(
        modules, get_rules(["jit-impure", "async-blocking"]))
    assert found == [], "sp prefill seam regressed:\n" + "\n".join(
        f.render() for f in found
    )


# --------------------------------------------------------------------------
# fleet simulator: virtual-time discipline under sim/
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_sim_modules_pass_async_blocking_and_task_leak():
    """The simulator's 1000x claim rests on the virtual loop never
    blocking for real: one time.sleep or sync file read inside a sim
    coroutine burns WALL time per virtual tick (the speedup gate in
    scripts/fleetsim.py would quietly decay to 1x), and a dropped
    worker/chaos/scrape task would outlive the run and corrupt the
    next scenario's determinism. Pin the whole package ZERO-finding,
    not baseline-covered."""
    sim = os.path.join(PACKAGE_ROOT, "sim")
    modules = [os.path.join(sim, name)
               for name in sorted(os.listdir(sim))
               if name.endswith(".py")]
    assert len(modules) >= 7  # the scan must actually see the package
    found = lint_paths(modules, get_rules(["async-blocking", "task-leak"]))
    assert found == [], "sim virtual-time discipline regressed:\n" + \
        "\n".join(f.render() for f in found)


def test_async_blocking_flags_sim_loop_sleeping_for_real():
    """TP fixture shaped like the tempting-but-wrong sim pacing: the
    arrival dispatcher waits out inter-arrival gaps with time.sleep —
    real seconds on the virtual loop, exactly the bug that turns a
    1000x replay back into real time."""
    out = findings(
        """
        import time

        async def dispatch_arrivals(requests, serve):
            last = 0.0
            for req in requests:
                time.sleep(req.arrival_s - last)   # real seconds!
                last = req.arrival_s
                serve(req)
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


def test_task_leak_flags_sim_serve_shaped_discarded_task():
    """TP fixture shaped like a careless request dispatcher: per-request
    serve tasks spawned without holding the handle can never be awaited
    at teardown, so a late completion leaks into the NEXT scenario's
    virtual clock and breaks byte-identical replay."""
    out = findings(
        """
        import asyncio

        class Fleet:
            def dispatch(self, req):
                asyncio.create_task(self._serve(req))

            async def _serve(self, req):
                await asyncio.sleep(1.0)
        """,
        "task-leak",
    )
    assert [f.rule for f in out] == ["task-leak"]


# --------------------------------------------------------------------------
# wallclock-in-sim: the simulator's virtual-time contract as a rule
# --------------------------------------------------------------------------

SIM_REL = "dynamo_tpu/sim/fixture_mod.py"


def sim_findings(src, rel=SIM_REL):
    return lint_source(textwrap.dedent(src), get_rules(["wallclock-in-sim"]),
                       rel=rel)


def test_wallclock_in_sim_flags_time_reads_and_sleep():
    out = sim_findings(
        """
        import time
        def sample():
            return time.time()
        def tick():
            time.sleep(0.1)
        """,
    )
    assert [f.line for f in out] == [4, 6]
    assert "time.time" in out[0].message and "time.sleep" in out[1].message


def test_wallclock_in_sim_resolves_aliases_and_datetime():
    out = sim_findings(
        """
        from time import monotonic as mono
        import datetime
        def sample():
            return mono(), datetime.datetime.now()
        """,
    )
    assert len(out) == 2
    assert {"time.monotonic", "datetime.datetime.now"} <= {
        m for f in out for m in [f.message.split("()")[0]]
    }


def test_wallclock_in_sim_flags_loop_time():
    out = sim_findings(
        """
        def drive(loop):
            return loop.time()
        """,
    )
    assert len(out) == 1 and "loop.time()" in out[0].message


def test_wallclock_in_sim_scoped_to_sim_package_only():
    """The identical source outside dynamo_tpu/sim/ is legitimate."""
    src = """
        import time
        def sample():
            return time.time()
    """
    assert sim_findings(src, rel="dynamo_tpu/telemetry/hub.py") == []
    assert sim_findings(src, rel="dynamo_tpu/sim_tools/x.py") == []
    assert len(sim_findings(src)) == 1


def test_wallclock_in_sim_does_not_flag_virtual_clock_idiom():
    """clock() calls routed through the scenario's VirtualClock — the
    sanctioned spelling — stay clean, as do mere mentions in strings."""
    assert sim_findings(
        """
        def sample(clock):
            return clock.now()  # "time.time" in a comment is fine
        """,
    ) == []


def test_wallclock_in_sim_suppression():
    out = sim_findings(
        """
        import time
        def seed_entropy():
            # dynlint: allow(wallclock-in-sim) - one-shot seed material, never consulted mid-run
            return time.time_ns()
        """,
    )
    assert out == []


@pytest.mark.dynlint
def test_sim_package_has_zero_wallclock_findings():
    """The rule that replaced test_fleetsim's regex scan must hold the
    same line: ZERO findings under sim/, not baseline-covered ones."""
    sim = os.path.join(PACKAGE_ROOT, "sim")
    assert lint_paths([sim], get_rules(["wallclock-in-sim"])) == []


# --------------------------------------------------------------------------
# dynrace: thread-domain inference
# --------------------------------------------------------------------------

from dynamo_tpu.analysis import SourceModule, infer_domains  # noqa: E402


def domains_of(src, rel="dynamo_tpu/fixture_mod.py"):
    mod = SourceModule(rel, textwrap.dedent(src))
    return infer_domains([mod])


def test_domains_async_def_is_loop():
    doms = domains_of(
        """
        async def pump():
            pass
        def untouched():
            pass
        """,
    )
    assert doms["dynamo_tpu/fixture_mod.py:pump"] == {"loop"}
    assert doms["dynamo_tpu/fixture_mod.py:untouched"] == set()


def test_domains_executor_lambda_and_thread_target():
    doms = domains_of(
        """
        import asyncio
        import threading

        class C:
            async def offload(self):
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, lambda: self.render())
            def render(self):
                pass
            def start(self):
                threading.Thread(target=self._drain, daemon=True).start()
            def _drain(self):
                pass
        """,
    )
    assert doms["dynamo_tpu/fixture_mod.py:C.offload.<lambda>"] == {"executor"}
    # the lambda's body calls render() -> executor propagates through
    assert doms["dynamo_tpu/fixture_mod.py:C.render"] == {"executor"}
    assert doms["dynamo_tpu/fixture_mod.py:C._drain"] == {"thread"}


def test_domains_fixpoint_through_two_hop_call_chain():
    doms = domains_of(
        """
        import threading

        class C:
            async def on_loop(self):
                self._mid()
            def _mid(self):
                self._leaf()
            def _leaf(self):
                pass
            def start(self):
                threading.Thread(target=self._mid).start()
        """,
    )
    # loop (via async caller) and thread (via Thread target) both reach
    # _leaf two hops down
    assert doms["dynamo_tpu/fixture_mod.py:C._mid"] == {"loop", "thread"}
    assert doms["dynamo_tpu/fixture_mod.py:C._leaf"] == {"loop", "thread"}


def test_domains_annotation_overrides_propagation():
    doms = domains_of(
        """
        class C:
            async def on_loop(self):
                self._helper()
            # dynrace: domain(executor)
            def _helper(self):
                pass
            # dynrace: domain(any)
            def _anywhere(self):
                pass
        """,
    )
    # pinned: the loop caller must NOT add its domain
    assert doms["dynamo_tpu/fixture_mod.py:C._helper"] == {"executor"}
    assert doms["dynamo_tpu/fixture_mod.py:C._anywhere"] == set()


def test_domains_call_soon_threadsafe_and_partial_unwrap():
    doms = domains_of(
        """
        import functools

        class C:
            # dynrace: domain(thread)
            def from_thread(self, loop):
                loop.call_soon_threadsafe(self._apply)
                loop.call_later(1.0, functools.partial(self._tick, 3))
            def _apply(self):
                pass
            def _tick(self, n):
                pass
        """,
    )
    assert doms["dynamo_tpu/fixture_mod.py:C._apply"] == {"loop"}
    assert doms["dynamo_tpu/fixture_mod.py:C._tick"] == {"loop"}


def test_domains_nested_def_inherits_enclosing_domain():
    doms = domains_of(
        """
        async def handler():
            def fmt(x):
                return x
            return fmt(1)
        """,
    )
    assert doms["dynamo_tpu/fixture_mod.py:handler.fmt"] == {"loop"}


# --------------------------------------------------------------------------
# dynrace: cross-domain-race findings and sanctioned idioms
# --------------------------------------------------------------------------


def race_findings(src):
    return findings(src, "cross-domain-race")


def test_race_flags_executor_render_iterating_loop_mutated_dict():
    """The PR 10 class verbatim: the /fleet render runs in the executor
    and iterates a registry the scrape loop mutates in place."""
    out = race_findings(
        """
        import asyncio

        class Hub:
            def __init__(self):
                self._workers = {}
            async def scrape_once(self, name, w):
                self._workers[name] = w
            async def handle_fleet(self):
                loop = asyncio.get_running_loop()
                return await loop.run_in_executor(None, self.render)
            def render(self):
                return [w.name for w in self._workers.values()]
        """,
    )
    assert len(out) == 1
    assert out[0].line == 13
    assert "_workers" in out[0].message and "executor" in out[0].message


def test_race_sanctions_list_snapshot_read():
    """Same shape, but the render materializes list(...) first — the
    repo's sanctioned GIL-atomic snapshot idiom must stay clean."""
    assert race_findings(
        """
        import asyncio

        class Hub:
            def __init__(self):
                self._workers = {}
            async def scrape_once(self, name, w):
                self._workers[name] = w
            async def handle_fleet(self):
                loop = asyncio.get_running_loop()
                return await loop.run_in_executor(None, self.render)
            def render(self):
                return [w.name for w in list(self._workers.values())]
        """,
    ) == []


def test_race_flags_write_write_across_domains():
    out = race_findings(
        """
        import threading

        class C:
            def __init__(self):
                self.cur = None
            async def on_loop(self):
                self.cur = object()
            # dynrace: domain(thread)
            def off_loop(self):
                self.cur = None
        """,
    )
    assert len(out) == 2 and {f.line for f in out} == {8, 11}


def test_race_sanctions_lock_held_on_both_sides():
    assert race_findings(
        """
        import threading

        class C:
            def __init__(self):
                self.vals = []
                self._lock = threading.Lock()
            # dynrace: domain(thread)
            def writer(self):
                with self._lock:
                    self.vals.append(1)
            async def reader(self):
                with self._lock:
                    return [v for v in self.vals]
        """,
    ) == []


def test_race_flags_lock_held_on_one_side_only():
    out = race_findings(
        """
        import threading

        class C:
            def __init__(self):
                self.vals = []
                self._lock = threading.Lock()
            # dynrace: domain(thread)
            def writer(self):
                with self._lock:
                    self.vals.append(1)
            async def reader(self):
                return [v for v in self.vals]
        """,
    )
    assert len(out) == 1 and out[0].line == 13


def test_race_sanctions_queue_handoff():
    assert race_findings(
        """
        import queue

        class C:
            def __init__(self):
                self.q = queue.Queue(maxsize=64)
            # dynrace: domain(thread)
            def producer(self):
                self.q.put(1)
            async def consumer(self):
                return self.q.get_nowait()
        """,
    ) == []


def test_race_sanctions_call_soon_threadsafe_marshal():
    """Thread-side code marshals the mutation onto the loop — the
    callback is inferred loop-domain, so all writes live in one domain."""
    assert race_findings(
        """
        class C:
            def __init__(self, loop):
                self.loop = loop
                self.hooks = []
            # dynrace: domain(thread)
            def from_thread(self):
                self.loop.call_soon_threadsafe(self._apply)
            def _apply(self):
                self.hooks.append(1)
            async def on_loop(self):
                self.hooks.append(2)
        """,
    ) == []


def test_race_sanctions_init_only_assignment_then_reads():
    assert race_findings(
        """
        class C:
            def __init__(self, cfg):
                self.cfg = cfg
            async def on_loop(self):
                return self.cfg
            # dynrace: domain(executor)
            def render(self):
                return self.cfg
        """,
    ) == []


def test_race_sanctions_rebind_publish_with_cross_domain_reads():
    """Loop-side rebinding to a FRESH object is an atomic pointer
    publish; off-loop readers see the old or new dict, never a torn
    one — the snapshot-publish idiom must not be flagged."""
    assert race_findings(
        """
        class C:
            def __init__(self):
                self.snap = {}
            async def refresh(self):
                self.snap = {"a": 1}
            # dynrace: domain(executor)
            def render(self):
                return dict(self.snap)
        """,
    ) == []


def test_race_flags_live_deque_iteration_across_domains():
    """The device_time class: reconciliation appends to a rolling deque
    on the loop while a render callback iterates it off-loop — deques
    raise RuntimeError when mutated mid-iteration."""
    out = race_findings(
        """
        import collections

        class Tracker:
            def __init__(self):
                self._window = collections.deque(maxlen=4096)
            async def observe(self, s):
                self._window.append(s)
            # dynrace: domain(executor)
            def _samples(self):
                return [s for s in self._window]
        """,
    )
    assert len(out) == 1 and out[0].line == 11
    # ...and the list() spelling of the same read is the sanctioned fix
    assert race_findings(
        """
        import collections

        class Tracker:
            def __init__(self):
                self._window = collections.deque(maxlen=4096)
            async def observe(self, s):
                self._window.append(s)
            # dynrace: domain(executor)
            def _samples(self):
                return [s for s in list(self._window)]
        """,
    ) == []


def test_race_flags_rmw_counter_in_two_domains():
    out = race_findings(
        """
        class C:
            def __init__(self):
                self.n = 0
            async def on_loop(self):
                self.n += 1
            # dynrace: domain(executor)
            def off(self):
                self.n += 1
        """,
    )
    assert len(out) == 2


def test_race_unknown_domain_produces_no_findings():
    """A function the graph never reaches has no inferred domain — the
    pass is conservative and must stay silent rather than guess."""
    assert race_findings(
        """
        class C:
            def __init__(self):
                self.vals = []
            def somewhere(self):
                self.vals.append(1)
            async def reader(self):
                for v in self.vals:
                    pass
        """,
    ) == []


def test_race_suppression_and_key_stability():
    src = """
        import asyncio

        class Hub:
            def __init__(self):
                self._workers = {}
            async def scrape_once(self, name, w):
                self._workers[name] = w
            async def handle_fleet(self):
                loop = asyncio.get_running_loop()
                return await loop.run_in_executor(None, self.render)
            def render(self):
                # dynlint: allow(cross-domain-race) - fixture: documented benign
                return [w.name for w in self._workers.values()]
    """
    assert race_findings(src) == []
    # finding keys are line-free for the baseline ratchet
    noisy = race_findings(src.replace(
        "# dynlint: allow(cross-domain-race) - fixture: documented benign",
        "pass"))
    assert noisy and ":cross-domain-race: " in noisy[0].key()
    assert str(noisy[0].line) not in noisy[0].key().split(":")[0]


def test_race_cross_module_domain_propagation_via_relative_import():
    """Domains must propagate through a call edge that crosses a module
    boundary via a relative import (core's alias map skips those —
    domains.py enriches it), and Thread(target=<imported name>) must
    seed the function defined in the OTHER module."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        pkg = os.path.join(td, "pkg")
        os.makedirs(pkg)
        open(os.path.join(pkg, "__init__.py"), "w").close()
        with open(os.path.join(pkg, "helpers.py"), "w") as f:
            f.write(textwrap.dedent(
                """
                def compute():
                    return 1
                """))
        with open(os.path.join(pkg, "owner.py"), "w") as f:
            f.write(textwrap.dedent(
                """
                import threading
                from .helpers import compute

                async def on_loop():
                    return compute()

                def start():
                    threading.Thread(target=compute).start()
                """))
        mods = []
        for name in ("helpers.py", "owner.py"):
            with open(os.path.join(pkg, name)) as f:
                mods.append(SourceModule(f"pkg/{name}", f.read()))
        doms = infer_domains(mods)
        # loop via the async caller in owner.py, thread via the Thread
        # target — both reached compute() across the module boundary
        assert doms["pkg/helpers.py:compute"] == {"loop", "thread"}


# --------------------------------------------------------------------------
# unified transfer plane (dynamo_tpu/transfer/)
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_transfer_plane_modules_pass_three_rule_screen():
    """Every KV byte in the system rides this package (disagg push,
    fabric pull, hot migration), so its discipline failures multiply:
    a blocking encode on the loop stalls all three planes at once, a
    dropped pump task strands a half-sent stream, and a cross-domain
    write on the shared poison/pipe state corrupts commit semantics
    under the executor offloads the framing itself performs. Pin the
    whole package ZERO-finding — not baseline-covered — on all three
    rules."""
    modules = [
        os.path.join(PACKAGE_ROOT, "transfer", "__init__.py"),
        os.path.join(PACKAGE_ROOT, "transfer", "framing.py"),
        os.path.join(PACKAGE_ROOT, "transfer", "plane.py"),
        os.path.join(PACKAGE_ROOT, "transfer", "tcp.py"),
        os.path.join(PACKAGE_ROOT, "transfer", "ici.py"),
    ]
    found = lint_paths(
        modules,
        get_rules(["async-blocking", "task-leak", "cross-domain-race"]),
    )
    assert found == [], "transfer-plane discipline regressed:\n" + \
        "\n".join(f.render() for f in found)


def test_async_blocking_flags_pack_on_loop_shape():
    """TP fixture shaped like a careless transfer backend: the frame
    encode spills through a blocking file write on the event loop —
    every other channel's pipelining stalls behind one sender's disk.
    (The real backends push encode_blocks through run_in_executor and
    only pack the small header inline.)"""
    out = findings(
        """
        import numpy as np

        async def send_frame(writer, k, v, spool_path):
            kb = np.ascontiguousarray(k).tobytes()
            with open(spool_path, "wb") as fh:
                fh.write(kb)
            writer.write(kb)
            await writer.drain()
        """,
        "async-blocking",
    )
    assert [f.rule for f in out] == ["async-blocking"]


# --------------------------------------------------------------------------
# dynrace: enforcement pins for the triaged serving-plane modules
# --------------------------------------------------------------------------


@pytest.mark.dynlint
def test_serving_plane_modules_pass_cross_domain_race():
    """The triage held the tree at zero un-suppressed findings; pin the
    hot modules individually so a regression names the file. These are
    the regression tests for this PR's fixes:

    - kv_router/metrics_aggregator.py: per-worker gauge callbacks and
      the staleness gauge iterated live dicts the poll loop mutates —
      now list() snapshots;
    - telemetry/device_time.py: _samples() iterated the live rolling
      deque the reconciliation seams append to — now a list() snapshot;
    - engine/scheduler.py: slot-occupancy gauges counted over the live
      slot table — now list() snapshots;
    - kv_router/recorder.py: FIFO single-worker executor serializes all
      _fh ops — suppressed inline with justification;
    - telemetry/hub.py: the PR 10 hardening (snapshot reads in the
      executor-side /fleet renders) proved clean under the detector.
    """
    modules = [
        os.path.join(PACKAGE_ROOT, "kv_router", "metrics_aggregator.py"),
        os.path.join(PACKAGE_ROOT, "kv_router", "recorder.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "device_time.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "hub.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "history.py"),
        os.path.join(PACKAGE_ROOT, "telemetry", "tracing.py"),
        os.path.join(PACKAGE_ROOT, "engine", "scheduler.py"),
        os.path.join(PACKAGE_ROOT, "kv", "cold_tier.py"),
    ]
    found = lint_paths(modules, get_rules(["cross-domain-race"]))
    assert found == [], "\n".join(f.render() for f in found)


@pytest.mark.dynlint
def test_whole_package_cross_domain_race_is_triaged():
    """Tree-wide: every cross-domain-race finding is fixed, suppressed
    inline with justification, or recorded in the baseline — zero
    un-triaged findings (the tentpole's acceptance bar)."""
    found = lint_paths([PACKAGE_ROOT], get_rules(["cross-domain-race"]))
    diff = diff_against_baseline(found, load_baseline(BASELINE))
    assert not diff.new, "\n".join(f.render() for f in diff.new)


# --------------------------------------------------------------------------
# CLI: --changed mode
# --------------------------------------------------------------------------


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_cli_changed_scopes_reporting_to_differing_files(tmp_path,
                                                         monkeypatch):
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)

    repo = tmp_path / "repo"
    pkg = repo / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "clean.py").write_text("x = 1\n")
    dirty = textwrap.dedent(
        """
        import time
        async def a():
            time.sleep(1)
        """)
    (pkg / "dirty.py").write_text(dirty)
    _git(repo, "init", "-q")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")

    monkeypatch.setattr(dynlint, "REPO_ROOT", str(repo))
    baseline = str(tmp_path / "b.json")

    # nothing changed vs HEAD -> clean exit, pre-existing debt unreported
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline, "--changed"]) == 0

    # touch the dirty file -> its finding is reported again
    (pkg / "dirty.py").write_text(dirty + "y = 2\n")
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline, "--changed"]) == 1
    # ...but only the clean file changing stays clean
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "touch dirty")
    (pkg / "clean.py").write_text("x = 3\n")
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline, "--changed"]) == 0
    # an untracked .py file is linted too
    (pkg / "fresh.py").write_text(dirty)
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline, "--changed"]) == 1
    # explicit ref form
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline,
         "--changed=HEAD"]) == 1


def test_cli_changed_filters_baseline_to_changed_files(tmp_path,
                                                       monkeypatch):
    """Debt recorded for UNCHANGED files must neither satisfy nor be
    reported stale by a --changed run."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)

    repo = tmp_path / "repo"
    pkg = repo / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    dirty = textwrap.dedent(
        """
        import time
        async def a():
            time.sleep(1)
        """)
    (pkg / "debt.py").write_text(dirty)
    (pkg / "other.py").write_text("x = 1\n")
    _git(repo, "init", "-q")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    monkeypatch.setattr(dynlint, "REPO_ROOT", str(repo))

    baseline = str(tmp_path / "b.json")
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline,
         "--update-baseline"]) == 0
    # only other.py changes: debt.py's baseline entry is out of scope,
    # must not be flagged stale (exit 0)
    (pkg / "other.py").write_text("x = 2\n")
    assert dynlint.main(
        ["dynlint", str(pkg), "--baseline", baseline, "--changed"]) == 0


def test_cli_changed_bad_ref_is_usage_error():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)
    assert dynlint.main(
        ["dynlint", "--changed=definitely-not-a-ref"]) == 2


def test_cli_list_rules_and_github_format_cover_new_rules(capsys):
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import dynlint
    finally:
        sys.path.pop(0)
    assert dynlint.main(["dynlint", "--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "cross-domain-race" in out and "wallclock-in-sim" in out
    # ::error rendering carries the rule name for CI annotations
    from dynamo_tpu.analysis import Finding
    gh = Finding("cross-domain-race", "dynamo_tpu/x.py", 3, "msg")
    assert gh.render_github().startswith(
        "::error file=dynamo_tpu/x.py,line=3,title=dynlint/cross-domain-race")


def test_project_rule_context_not_shrunk_by_changed_scope(tmp_path):
    """only_files restricts REPORTING, not parsing: a cross-module race
    must be reported on a changed file even when the other half of the
    race lives in an unchanged module."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "writer.py").write_text(textwrap.dedent(
        """
        class W:
            def __init__(self):
                self.vals = []
            async def on_loop(self):
                self.vals.append(1)
            # dynrace: domain(executor)
            def render(self):
                return [v for v in self.vals]
        """))
    (pkg / "other.py").write_text("x = 1\n")
    rules = get_rules(["cross-domain-race"])
    scoped = lint_paths([str(pkg)], rules, only_files={"pkg/writer.py"})
    assert [f.file for f in scoped] == ["pkg/writer.py"]
    # scoping to the OTHER file hides the finding without losing it
    assert lint_paths([str(pkg)], rules, only_files={"pkg/other.py"}) == []
