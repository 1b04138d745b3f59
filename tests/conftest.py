"""Test environment: force JAX onto 8 virtual CPU devices.

This is the framework's no-cluster analog of the reference's ``-t`` smoke
mode (N× ``localhost`` workers, reference ``README.md:29``): a single host
pretending to be an 8-shard mesh, per SURVEY.md §4. Must run before anything
imports jax.
"""

import os

# tests always run on the virtual 8-device CPU mesh, even on a machine
# with a chip: set before any backend initializes
os.environ["JAX_PLATFORMS"] = "cpu"
# arm the runtime lock-order detector for the whole suite: every
# threaded serving/replication/obs test doubles as a lock-order
# regression check (utils.locks witness graph; cycles raise at the
# acquire that would make deadlock possible)
os.environ.setdefault("DOS_LOCK_CHECK", "1")
# pin the walk-kernel knob for tier-1: the XLA walk is the reference
# path every existing suite runs on, and the Pallas-fused kernel is
# exercised EXPLICITLY by tests/test_pallas_walk.py in interpret mode
# (it opts in per test). A hard override — not setdefault — so a
# container env carrying DOS_WALK_KERNEL=pallas can neither slow the
# whole suite to interpret speed nor let the parity suite silently
# stop comparing the two kernels against each other.
os.environ["DOS_WALK_KERNEL"] = "xla"
# same rule for the resident-codec knob: raw residency is the reference
# path every existing suite pins bit-identity against, and compressed
# residency is exercised EXPLICITLY by tests/test_compressed.py (it
# opts in per test). A container env carrying DOS_CPD_RESIDENT=rle
# must not silently flip every engine in the suite.
os.environ["DOS_CPD_RESIDENT"] = "raw"
# the same 8 devices for child processes that tests start
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# no persistent compile cache in tests: entry points called in-process
# point it at the checkout (utils.compile_cache), and tests must not
# read programs another run compiled
jax.config.update("jax_enable_compilation_cache", False)
assert len(jax.devices()) == 8, jax.devices()

import glob
import stat
import threading
import time

import numpy as np
import pytest

from distributed_oracle_search_tpu.data import synth_city_graph, synth_scenario
from distributed_oracle_search_tpu.obs import metrics as obs_metrics
from distributed_oracle_search_tpu.testing import faults
from distributed_oracle_search_tpu.utils import locks as dos_locks


@pytest.fixture(scope="session", autouse=True)
def _no_lock_order_cycles():
    """The witness graph must stay acyclic across the WHOLE run: in
    warn mode (or if a raise was swallowed by a worker thread) the
    session still fails with the recorded violation list."""
    yield
    assert dos_locks.violations() == [], dos_locks.violations()


@pytest.fixture(scope="session")
def toy_graph():
    """8x6 city grid — small enough for O(N^2) golden oracles."""
    return synth_city_graph(8, 6, seed=7)


@pytest.fixture(scope="session")
def toy_queries(toy_graph):
    return synth_scenario(toy_graph.n, 64, seed=11)


def _shared_dir_fifos() -> set:
    """FIFOs in /tmp matching the transport's naming conventions — the
    default shared dir, where a leak would poison later runs."""
    out = set()
    for pat in ("/tmp/worker*.fifo", "/tmp/answer.*"):
        for p in glob.glob(pat):
            try:
                if stat.S_ISFIFO(os.stat(p).st_mode):
                    out.add(p)
            except OSError:
                continue
    return out


@pytest.fixture(autouse=True)
def _no_leaked_fault_tolerance_resources():
    """Every test must clean up after the fault-tolerance layer: no
    ``dos-*`` supervisor/probe thread still alive, the supervisor gauge
    back at zero (checked via a metrics snapshot), no new FIFO left in
    the shared /tmp dir, and no armed fault injector bleeding into the
    next test."""
    fifos_before = _shared_dir_fifos()
    threads_before = {t.name for t in threading.enumerate()
                      if t.name.startswith("dos-")}
    yield
    faults.reset()
    # daemon probe threads notice shutdown on their next wait tick —
    # allow a short grace before calling a thread leaked
    deadline = time.monotonic() + 3.0
    leaked = []
    while time.monotonic() < deadline:
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith("dos-") and t.is_alive()
                  and t.name not in threads_before]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"leaked supervisor/probe threads: {leaked}"
    snap = obs_metrics.REGISTRY.snapshot()
    alive = snap["gauges"].get("supervisor_workers_alive", 0)
    assert alive == 0, f"supervisor gauge reports {alive} workers alive"
    fifos_after = _shared_dir_fifos()
    assert fifos_after <= fifos_before, (
        f"leaked FIFOs in /tmp: {sorted(fifos_after - fifos_before)}")
