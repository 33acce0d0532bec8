"""Test fixtures.

Mirrors the reference's test strategy (SURVEY.md §4): a real local "cluster"
fixture (here: an 8-device CPU mesh via ``--xla_force_host_platform_device_count``,
the JAX analog of Spark ``local[8]``), small Keras model factories, and tiny
synthetic datasets.

Environment: tests run on the CPU, never on the chip::

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 KERAS_BACKEND=jax \
    python -m pytest tests/ -x -q

(`make test` does exactly this.) The settings below are a best-effort fallback
for when jax has not yet initialized a backend.
"""

import os

os.environ.setdefault("KERAS_BACKEND", "jax")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import faulthandler
import re
import sys
import threading

import numpy as np
import pytest

# The 8-device CPU mesh has one known flake: XLA's CPU collective rendezvous
# can starve in long tight loops (CollectivePermute timeout / rendezvous
# deadlock — see docs/DISTRIBUTED.md). Tests keep step counts small to avoid
# it, but the harness must not rely on that convention alone: a failure whose
# output matches the signature is retried ONCE. Anything else fails normally
# — this must never mask a real bug, so the pattern is deliberately narrow.
_COLLECTIVE_FLAKE = re.compile(
    r"CollectivePermute"
    r"|[Rr]endezvous.{0,120}(tim(e|ed)[ -]?out|abort|deadlock|starv)"
    r"|(tim(e|ed)[ -]?out|deadlock|starv\w*).{0,120}[Rr]endezvous",
    re.DOTALL,
)

# Flake-retry accounting: the retry must never silently mask a RISING flake
# rate (a newly introduced intermittent deadlock pattern-matches the flake
# signature). Every retry is counted and reported in the terminal summary;
# past ELEPHAS_MAX_FLAKE_RETRIES (default 5) the run FAILS even if every
# retried test eventually passed.
_flake_retries: list = []  # nodeids that hit the retry path

# Per-test hang watchdog. A starved CPU-collective rendezvous does not
# always error out — it can wedge the process, and pytest (single-process,
# no pytest-timeout in this image) would sit until the CI job bound.
# A timer thread converts the hang into a fast, attributable failure: dump
# every thread's stack, record the culprit nodeid in ELEPHAS_WATCHDOG_FILE,
# and hard-exit with code 42 (scripts/run_tests.sh reruns the suite once and
# deselects the test if it hangs twice). A blocked XLA collective cannot be
# interrupted from Python, so killing the process is the only honest option.
# Override per test with @pytest.mark.timeout(seconds) for legitimately slow
# tests, or globally with ELEPHAS_TEST_TIMEOUT (0 disables). The default is
# sized from the measured suite profile (slowest non-example test ≈ 70s
# locally) with ~4x headroom for slower CI runners — a real hang still
# surfaces in minutes, not the job bound.
_WATCHDOG_DEFAULT = float(os.environ.get("ELEPHAS_TEST_TIMEOUT", "300"))
_WATCHDOG_EXIT_CODE = 42


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test hang-watchdog bound (conftest watchdog, "
        "not pytest-timeout)",
    )


def _watchdog_abort(nodeid: str, seconds: float) -> None:
    # pytest's capture machinery owns stderr and os._exit skips its flush, so
    # anything written there is lost. The watchdog file (read and echoed by
    # scripts/run_tests.sh) is the one channel guaranteed to survive: nodeid
    # on line 1, full all-thread stack dump after it.
    msg = (
        f"[conftest] WATCHDOG: {nodeid} still running after {seconds:.0f}s "
        f"— dumping stacks and aborting the process (exit "
        f"{_WATCHDOG_EXIT_CODE})\n"
    )
    path = os.environ.get("ELEPHAS_WATCHDOG_FILE")
    if path:
        try:
            with open(path, "w") as f:
                f.write(nodeid + "\n" + msg)
                faulthandler.dump_traceback(file=f)
        except OSError:
            pass
    try:
        os.write(2, ("\n" + msg).encode())  # best effort if fd 2 is a tty
    except OSError:
        pass
    os._exit(_WATCHDOG_EXIT_CODE)


def pytest_runtest_protocol(item, nextitem):
    from _pytest.runner import runtestprotocol

    hook = item.ihook
    hook.pytest_runtest_logstart(nodeid=item.nodeid, location=item.location)

    marker = item.get_closest_marker("timeout")
    if marker:  # positional or pytest-timeout-style seconds= keyword
        seconds = float(
            marker.args[0] if marker.args
            else marker.kwargs.get("seconds", _WATCHDOG_DEFAULT)
        )
    else:
        seconds = _WATCHDOG_DEFAULT

    def run_once():
        if seconds > 0:
            timer = threading.Timer(
                seconds, _watchdog_abort, args=(item.nodeid, seconds))
            timer.daemon = True
            timer.start()
            try:
                return runtestprotocol(item, nextitem=nextitem, log=False)
            finally:
                timer.cancel()
        return runtestprotocol(item, nextitem=nextitem, log=False)

    reports = run_once()
    if any(
        r.when == "call" and r.failed
        and _COLLECTIVE_FLAKE.search(str(r.longrepr))
        for r in reports
    ):
        _flake_retries.append(item.nodeid)
        sys.stderr.write(
            f"\n[conftest] known CPU-collective rendezvous flake in "
            f"{item.nodeid}; retrying once "
            f"(retry #{len(_flake_retries)} this run)\n"
        )
        reports = run_once()
    for report in reports:
        hook.pytest_runtest_logreport(report=report)
    hook.pytest_runtest_logfinish(nodeid=item.nodeid, location=item.location)
    return True


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if _flake_retries:
        terminalreporter.write_sep(
            "=", f"collective-flake retries: {len(_flake_retries)}")
        for nodeid in _flake_retries:
            terminalreporter.write_line(f"  retried: {nodeid}")


def pytest_sessionfinish(session, exitstatus):
    max_retries = int(os.environ.get("ELEPHAS_MAX_FLAKE_RETRIES", "5"))
    if len(_flake_retries) > max_retries and session.exitstatus == 0:
        sys.stderr.write(
            f"\n[conftest] {len(_flake_retries)} flake retries fired this "
            f"run (> ELEPHAS_MAX_FLAKE_RETRIES={max_retries}) — the flake "
            f"rate is rising; failing the run so it gets looked at\n"
        )
        session.exitstatus = 1


@pytest.fixture(scope="session")
def spark_context():
    from elephas_tpu.data import SparkContext

    sc = SparkContext(master="local[8]", appName="elephas-tpu-tests")
    yield sc
    sc.stop()


@pytest.fixture(scope="session")
def spark_session():
    from elephas_tpu.data import SparkSession

    session = SparkSession.builder.master("local[8]").appName("tests").getOrCreate()
    yield session


@pytest.fixture(scope="session")
def toy_classification():
    """Linearly-separable-ish 3-class problem: (X [640,10], Y one-hot [640,3])."""
    rng = np.random.default_rng(42)
    n, d, c = 640, 10, 3
    x = rng.normal(size=(n, d)).astype("float32")
    w = rng.normal(size=(d, c))
    y = np.eye(c, dtype="float32")[(x @ w).argmax(axis=1)]
    return x, y


@pytest.fixture(scope="session")
def toy_regression():
    rng = np.random.default_rng(7)
    n, d = 512, 8
    x = rng.normal(size=(n, d)).astype("float32")
    w = rng.normal(size=(d,))
    y = (x @ w + 0.05 * rng.normal(size=(n,))).astype("float32")
    return x, y


def make_classifier(input_dim=10, nb_classes=3, hidden=32, optimizer="adam"):
    import keras

    model = keras.Sequential(
        [
            keras.layers.Dense(hidden, activation="relu"),
            keras.layers.Dense(nb_classes, activation="softmax"),
        ]
    )
    model.build((None, input_dim))
    model.compile(
        optimizer=optimizer, loss="categorical_crossentropy", metrics=["accuracy"]
    )
    return model


def make_regressor(input_dim=8, hidden=16):
    import keras

    model = keras.Sequential(
        [keras.layers.Dense(hidden, activation="relu"), keras.layers.Dense(1)]
    )
    model.build((None, input_dim))
    model.compile(optimizer="adam", loss="mse")
    return model


@pytest.fixture
def classifier_factory():
    return make_classifier


@pytest.fixture
def regressor_factory():
    return make_regressor
