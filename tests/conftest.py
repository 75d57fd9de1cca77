"""Test config: force an 8-device virtual CPU platform BEFORE jax imports.

This is the analog of the reference's artificial agent slots
(``agent/internal/detect/detect.go:40-57``) + thread-rank simulator
(``harness/tests/parallel.py``): all sharding/mesh tests run on CPU with 8
virtual devices, no TPU required.

What tier-1 may cost (PERF.md "What tier-1 costs").  The driver runs
``-n 6 --dist loadfile`` under a clock that cuts the run, and what the cut
does not reach is the end of the alphabet, so:

- a FILE is the unit of balance: run alone while five others run, no file
  outside ``tests/benchmark/`` takes more than 150 s.  One that does is made
  cheaper (the least shapes that cross the edges its tests name, one jitted
  program where a bare call compiles an operation at a time, module fixtures
  for what is read and not written) or cut in two along a seam it already
  has, its shared helpers in ``tests/model_cases.py``;
- a TEST has ``TEST_TIME_LIMIT_S`` (``_time_limit`` below): one that waits
  longer fails by its name with every thread's stack, and the run goes on;
- ``slow`` is the last resort: only for a test still over 30 s whose
  assertions a cheaper test that stays makes too, and CHANGES.md names both.
"""

import contextlib
import faulthandler
import os
import signal
import tempfile
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
# The CPU here answers for numerics and control flow, not for what its code
# generator makes of a program (the TPU's compiler is asked by
# tests/test_tpu_compile*.py, whose assertions on memory and kernels hold
# under these flags as without them): LLVM at level 0
# without its expensive passes compiles the suite's thousands of small programs
# in two thirds of the CPU seconds (68 -> 47 minutes of a run; PERF.md "What
# tier-1 costs"), and the processes the tests start inherit it.
_CPU_FLAGS = {
    "xla_force_host_platform_device_count": "8",
    "xla_backend_optimization_level": "0",
    "xla_llvm_disable_expensive_passes": "true",
}
prev = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = " ".join([prev] + [f"--{k}={v}" for k, v in _CPU_FLAGS.items() if k not in prev]).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Every test compiles what it runs, in every run: the program now always
# picks a persistent cache directory (utils/compilation_cache.py), and a
# suite served from a warm one would time and trace differently from a
# cold checkout's.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "faults: fault-injection tests (crash/corrupt/drop-peer; tier-1, tight timeouts)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` run; the last resort "
        "(still over 30 s, and a cheaper test that stays asserts the same), "
        "and each use has its line in CHANGES.md",
    )
    config.addinivalue_line(
        "markers",
        "no_thread_leaks: assert no dtpu-* worker threads survive the test "
        "(lint.ThreadLeakChecker; opt in per module/test)",
    )
    config.addinivalue_line(
        "markers",
        "lock_order: record the test's actual lock-acquisition DAG and fail "
        "on an observed ordering inversion (lint.LockOrderSentinel; opt in "
        "per module/test)",
    )
    config.addinivalue_line(
        "markers",
        "no_lock_order: per-test opt-out from a module-level lock_order mark "
        "(for wall-clock-ratio assertions the instrumentation would skew)",
    )
    config.addinivalue_line(
        "markers",
        "devcluster: needs the native master+agent binaries (native/build or "
        "DTPU_NATIVE_BUILD_DIR); skipped cleanly when they are not built — "
        "scripts/devcluster.sh builds them",
    )
    config.addinivalue_line(
        "markers",
        "collective_order: run with the control-plane collective entry "
        "points wrapped by lint.CollectiveSequenceSentinel — every "
        "DistributedContext created in the test digests its collective "
        "sequence and a rank-divergent sequence raises a named "
        "CollectiveDivergenceError instead of hanging (opt in per "
        "module/test)",
    )
    config.addinivalue_line(
        "markers",
        "no_collective_order: per-test opt-out from a module-level "
        "collective_order mark (for tests that drive raw payloads through "
        "the star transports)",
    )


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``devcluster``-marked tests when the native binaries are
    absent, the same way ``needs_cluster`` used to — but as a first-class
    marker so `-m devcluster` selects the whole cluster suite."""
    try:
        from scripts.devcluster import binaries_built
    except ImportError:
        # pytest not launched from the repo root: fall back to the path probe
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        build = os.environ.get(
            "DTPU_NATIVE_BUILD_DIR", os.path.join(repo, "native", "build")
        )

        def binaries_built():
            return os.path.exists(os.path.join(build, "dtpu-master")) and os.path.exists(
                os.path.join(build, "dtpu-agent")
            )

    if binaries_built():
        return
    skip = pytest.mark.skip(
        reason="native binaries not built (scripts/devcluster.sh builds them)"
    )
    for item in items:
        if item.get_closest_marker("devcluster") is not None:
            item.add_marker(skip)


#: seconds a test may take, its function-scoped fixtures included: three times
#: the slowest test of the suite under the driver's command when this was set
#: (PERF.md "What tier-1 costs").  A constant: no marker raises it, no option sets it.
TEST_TIME_LIMIT_S = 180.0


@contextlib.contextmanager
def time_limit(nodeid: str):
    """Fail what runs inside by name after ``TEST_TIME_LIMIT_S``: SIGALRM's
    handler raises in the main thread with ``nodeid`` and every thread's stack
    (a wait on a lock, a join, a sleep and a child's exit are all interrupted;
    a call that holds the interpreter, such as a compile, is failed when it
    returns).  Nothing off the main thread, where no handler can be set, nor
    on a platform without SIGALRM."""
    if not hasattr(signal, "SIGALRM") or threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        with tempfile.TemporaryFile("w+") as dump:
            faulthandler.dump_traceback(file=dump, all_threads=True)
            dump.seek(0)
            stacks = dump.read()
        pytest.fail(f"{nodeid} took more than {TEST_TIME_LIMIT_S:g} s (tests/conftest.py TEST_TIME_LIMIT_S)\n{stacks}", pytrace=False)

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _time_limit(request):
    """A test that hangs costs the run that test, not every test behind it in
    a worker's queue (the driver's clock cut 23 of them at PR 44).  The
    ``timeout=`` arguments inside the fault tests guard other things and stay."""
    with time_limit(request.node.nodeid):
        yield


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """Autouse, opt-in: tests/modules marked ``no_thread_leaks`` fail if a
    harness worker thread (dtpu-*) outlives them.  Leaked prefetch or
    scheduler workers otherwise bleed between tests and turn unrelated
    failures flaky — the runtime half of the preflight analyzer
    (determined_tpu/lint) makes the leak the failure."""
    if request.node.get_closest_marker("no_thread_leaks") is None:
        yield
        return
    from determined_tpu.lint import ThreadLeakChecker

    with ThreadLeakChecker(
        watch=("dtpu-*",), grace=5.0, scope=request.node.nodeid
    ):
        yield


@pytest.fixture(autouse=True)
def _lock_order_guard(request):
    """Autouse, opt-in: tests/modules marked ``lock_order`` run with
    ``threading.Lock``/``RLock`` patched to record the acquisition DAG;
    an observed inversion (the dynamic form of the static
    ``lock-order-cycle`` rule) fails the test deterministically — on the
    ORDER being contradictory, not on whether this run happened to
    interleave into the actual deadlock."""
    if (
        request.node.get_closest_marker("lock_order") is None
        or request.node.get_closest_marker("no_lock_order") is not None
    ):
        yield
        return
    from determined_tpu.lint import LockOrderSentinel

    sentinel = LockOrderSentinel()
    with sentinel:
        yield
    violations = sentinel.violations()
    assert not violations, "\n".join(v.format() for v in violations)


@pytest.fixture(autouse=True)
def _collective_order_guard(request):
    """Autouse, opt-in: tests/modules marked ``collective_order`` run with
    ``DistributedContext``'s collective methods wrapped by the
    collective-sequence sentinel — the dynamic form of the static SPMD
    rules: every rank's (op, payload-structure) sequence is digested and
    exchanged in-band, so a divergence raises a deterministic named error
    at the next collective instead of parking the peers until timeout."""
    if (
        request.node.get_closest_marker("collective_order") is None
        or request.node.get_closest_marker("no_collective_order") is not None
    ):
        yield
        return
    from determined_tpu.lint import CollectiveSequenceSentinel

    sentinel = CollectiveSequenceSentinel()
    with sentinel:
        yield
    # divergences raise inline at the collective; anything recorded but
    # swallowed by test code still fails the test here
    violations = sentinel.violations()
    assert not violations, "\n".join(str(v) for v in violations)


@pytest.fixture(autouse=True)
def _no_leaked_fault_injector():
    """A test that forgets to uninstall its FaultInjector must not poison
    the rest of the suite."""
    from determined_tpu.utils import faults

    yield
    faults.set_fault_injector(None)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(autouse=True)
def _isolated_auth_cache(tmp_path, monkeypatch):
    """Keep CLI/SDK token caches out of the real ~/.dtpu."""
    monkeypatch.setenv("DTPU_AUTH_PATH", str(tmp_path / "auth.json"))


@pytest.fixture()
def tmp_storage(tmp_path):
    return str(tmp_path / "storage")
