"""Test harness setup: force JAX onto CPU with 8 virtual devices so the whole
suite (sharding, mesh, collectives, e2e) runs without TPU hardware — the
TPU-native analogue of the reference's in-process MiniCluster test strategy
(tony-mini/.../MiniCluster.java:43-65, TestTonyE2E.java:90-109)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import jax  # noqa: E402

# tests run on the CPU whatever the host holds: pin the platform in the
# config too, before any backend initialization
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------- watchdog
# Per-test watchdog: a HUNG test (a serving-loop deadlock, a waiter that
# never wakes) must fail fast with a stack trace of every thread instead of
# silently eating the tier-1 gate's whole 870s budget. faulthandler's timer
# dumps all thread stacks and hard-exits the process — blunt, but a hang
# has no cooperative way out, and the dump names the guilty frame.
# Budget: TONY_TEST_WATCHDOG_S env (0 disables); @pytest.mark.slow tests
# (compile-bound, excluded from tier-1) get 3x.

import faulthandler  # noqa: E402

try:
    _WATCHDOG_S = float(os.environ.get("TONY_TEST_WATCHDOG_S", "300"))
except ValueError:      # bad knob degrades to the default, never aborts
    _WATCHDOG_S = 300.0


def _watchdog_budget(item) -> float:
    if _WATCHDOG_S <= 0:
        return 0.0
    mult = 3.0 if item.get_closest_marker("slow") else 1.0
    return _WATCHDOG_S * mult


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    # tryfirst: arm before the runner starts fixture setup, so a hang
    # INSIDE a fixture is covered too
    budget = _watchdog_budget(item)
    if budget > 0:
        faulthandler.dump_traceback_later(budget, exit=True)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    # wrapper: the watchdog stays armed THROUGH fixture finalizers (a
    # hang in e.g. a deadlocked ServeApp.shutdown is covered) and the
    # finally-cancel runs even when a finalizer raises — a plain trylast
    # impl would be skipped by the re-raise, leaving the hard-exit timer
    # live into session teardown
    try:
        yield
    finally:
        if _WATCHDOG_S > 0:
            faulthandler.cancel_dump_traceback_later()


# ------------------------------------------------------------- env flakes
# @pytest.mark.env_flaky — ONE automatic rerun on failure. Reserved for
# tests whose failures are a known ENVIRONMENT flake, identical on an
# unmodified checkout (the container's jax CPU gloo-collective
# availability comes and goes across the day — ROADMAP "known flakes");
# a genuine regression still fails both attempts and reports normally.
# Only the final attempt's reports are logged, so pass counts stay
# honest (one dot per test either way).

@pytest.hookimpl(tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    if item.get_closest_marker("env_flaky") is None:
        return None
    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        print(f"\n[env_flaky] {item.nodeid} failed; rerunning once "
              "(known environment flake)", flush=True)
        # drop the first attempt's (already-finalized) fixture instances
        # so the rerun gets FRESH setup — _fillfixtures skips argnames
        # already present in item.funcargs, which would otherwise hand
        # the retry stale tmp dirs (pytest-rerunfailures does the same)
        if hasattr(item, "_initrequest"):
            item._initrequest()
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True


# ------------------------------------------------------ tier-1 wall budget
# Per-test call durations, collected for the wall-budget guard
# (tests/test_budget_lint.py): a single non-slow test creeping past the
# per-test ceiling is how the 870s tier-1 gate historically overflowed
# (ROADMAP "budget is VERY thin"), and this surfaces the offender by
# NAME instead of as a mysterious whole-gate timeout. The lint test is
# reordered to run LAST so it sees every test of the session; durations
# cover the call phase (fixtures excluded — parallel to --durations).

TEST_DURATIONS: dict[str, float] = {}
SLOW_NODEIDS: set[str] = set()


@pytest.hookimpl
def pytest_runtest_logreport(report):
    if report.when == "call":
        TEST_DURATIONS[report.nodeid] = report.duration


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow"):
            SLOW_NODEIDS.add(item.nodeid)
    tail = [i for i in items if "test_tier1_wall_budget" in i.nodeid]
    if tail:
        head = [i for i in items if "test_tier1_wall_budget" not in i.nodeid]
        items[:] = head + tail


@pytest.fixture
def tmp_job_dirs(tmp_path):
    """Staging + history dirs for orchestration tests."""
    staging = tmp_path / "staging"
    history = tmp_path / "history"
    staging.mkdir()
    history.mkdir()
    return {"staging": str(staging), "history": str(history)}


FIXTURE_SCRIPTS = REPO_ROOT / "tests" / "fixtures" / "scripts"


@pytest.fixture
def fixture_script():
    def _get(name: str) -> str:
        path = FIXTURE_SCRIPTS / name
        assert path.exists(), f"missing fixture script {name}"
        return str(path)

    return _get
