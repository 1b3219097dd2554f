"""Test harness: run everything on a virtual 8-device CPU mesh.

This is the JAX-native analog of the reference's 'centered mode' fake
backend (SURVEY.md §4): all collective code paths execute in CI without a
TPU by forcing the host platform to expose 8 devices.

Must run before jax is imported anywhere.
"""
import os

# Force CPU even when the ambient environment selects a TPU platform:
# the test mesh is always the virtual host mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Eight virtual devices meet in every collective of a sharded program,
# one thread each. XLA:CPU ABORTS the process when a participant has
# not joined within its termination timeout (tens of seconds by
# default). The driver's runs of PRs 42 and 43 lost a worker that way
# (``Fatal Python error: Aborted`` inside the scalar program's dispatch
# in tests/test_round_scalars.py's [100-pod2] case, six xdist workers
# of eight devices each on a machine shared with other sandboxes; the
# case passes alone and under six-fold load, PR 44): the message is
# not in those logs, so the cause is inferred, not read. A late thread
# is waited for instead; a true deadlock still ends, after 240 s.
if "xla_cpu_collective_call_terminate_timeout_seconds" not in _flags:
    _flags += " --xla_cpu_collective_call_terminate_timeout_seconds=240"
os.environ["XLA_FLAGS"] = _flags

import pytest  # noqa: E402

# -- Suite tiering ----------------------------------------------------------
# tests/slow_tests.txt lists nodeids measured >= the threshold on the
# 1-core reference box (scripts/tier_tests.py regenerates it from a
# --durations=0 log). They get the `slow` marker automatically, so
#   pytest -m "not slow"    is the fast lane (<5 min on that box)
#   pytest tests/           still runs everything.
# Explicit @pytest.mark.slow decorations (multi-process tests) remain.
_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


def _slow_nodeids():
    try:
        with open(_SLOW_LIST) as f:
            return {line.split("#", 1)[0].strip() for line in f
                    if line.strip() and not line.startswith("#")}
    except OSError:
        return set()


def _advise(config, msg):
    """Print an advisory without the warnings machinery: under a
    project/user ``filterwarnings = error`` a collection-time
    ``warnings.warn`` would abort collection of the whole suite, and a
    degraded fast lane must never cost the full one."""
    import sys
    tr = config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_line("conftest: " + msg, yellow=True)
    else:
        print("conftest: " + msg, file=sys.stderr)


def pytest_collection_modifyitems(config, items):
    slow = _slow_nodeids()
    if not slow:
        _advise(config, "tests/slow_tests.txt missing or empty — the "
                "fast lane (-m 'not slow') will run slow tests; "
                "regenerate with scripts/tier_tests.py")
        return
    matched = set()
    for item in items:
        if item.nodeid in slow:
            matched.add(item.nodeid)
            item.add_marker(pytest.mark.slow)
    # surface staleness: a renamed test or changed parametrize id would
    # otherwise silently re-enter the fast lane. Only judge entries
    # whose FILE was collected in this run (path-restricted runs never
    # warn spuriously), and skip entirely when the invocation selects
    # individual node ids or deselects tests — then partial matches
    # are expected, not stale.
    if any("::" in a for a in config.args) \
            or config.getoption("deselect", None) \
            or config.getoption("keyword", None):
        return
    collected_files = {item.nodeid.split("::", 1)[0] for item in items}
    unmatched = {s for s in slow - matched
                 if s.split("::", 1)[0] in collected_files}
    if unmatched:
        _advise(config, f"{len(unmatched)} entries in tests/slow_tests.txt "
                "match no collected test (stale after a rename?); "
                "regenerate with scripts/tier_tests.py: "
                + ", ".join(sorted(unmatched)[:3]) + " ...")
