"""Test config: force an 8-device virtual CPU platform BEFORE jax imports.

This is the analog of the reference's artificial agent slots
(``agent/internal/detect/detect.go:40-57``) + thread-rank simulator
(``harness/tests/parallel.py``): all sharding/mesh tests run on CPU with 8
virtual devices, no TPU required.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

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
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run"
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
