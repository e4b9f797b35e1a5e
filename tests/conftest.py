"""Shared test configuration: hang-guard fallback for bare environments.

pyproject.toml sets ``timeout`` / ``timeout_method`` for pytest-timeout
(the CI hang guard — a deadlocked concurrency test must fail in seconds
with a stack trace, not eat the job timeout).  On environments without
the plugin those ini options would be unknown (config warning, no
guard), so this conftest degrades gracefully:

* it registers the two ini options itself, silencing the unknown-option
  warning, and
* arms a ``faulthandler.dump_traceback_later`` watchdog around every
  test — if a test outlives the timeout, every thread's stack is dumped
  to stderr and the process exits non-zero (coarser than pytest-timeout,
  which fails just the one test, but the diagnostic is the same).

When pytest-timeout IS installed, the hang guard here does nothing.

Before anything imports JAX, this file also asks XLA for 8 host (CPU)
devices, so the sharded tests (``-m shard``, DESIGN.md §10) run in every
tier-1 run instead of skipping.  Flags already in ``XLA_FLAGS`` are kept,
and a device count already set there wins.  The flag acts only on the
host platform; a run on an accelerator sees its own devices.
"""
from __future__ import annotations

import faulthandler
import os

_DEVICE_COUNT = "--xla_force_host_platform_device_count"
if _DEVICE_COUNT not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"{_DEVICE_COUNT}=8")))

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PLUGIN = True
except ImportError:
    _HAVE_PLUGIN = False


def pytest_addoption(parser):
    if _HAVE_PLUGIN:
        return
    parser.addini("timeout", "fallback per-test timeout in seconds "
                  "(pytest-timeout not installed)", default=None)
    parser.addini("timeout_method", "ignored by the fallback (kept so "
                  "pyproject.toml parses cleanly)", default="thread")


def pytest_runtest_protocol(item):
    if _HAVE_PLUGIN:
        return None
    try:
        timeout = float(item.config.getini("timeout") or 0)
    except (TypeError, ValueError):
        timeout = 0.0
    if timeout > 0:
        # dump ALL thread stacks and kill the process if the test hangs;
        # cancelled in pytest_runtest_teardown below on normal completion
        faulthandler.dump_traceback_later(timeout, exit=True)
    return None


def pytest_runtest_teardown(item):
    if not _HAVE_PLUGIN:
        faulthandler.cancel_dump_traceback_later()
