"""Multi-host (multi-process) mesh support.

The reference scales across hosts with ssh + NFS + FIFOs; the TPU-native
equivalent is multi-controller JAX: every host runs the same program,
``jax.distributed.initialize`` wires them into one runtime, and the worker
mesh simply spans all processes' devices — GSPMD then routes collectives
over ICI within a slice and DCN across hosts (SURVEY.md §5 "distributed
communication backend", build plan stage 6).

Cluster-conf integration: a ``multihost`` object in the conf JSON::

    "multihost": {"coordinator": "10.0.0.1:8476",
                  "num_processes": 4}        # process_id from env/flag

Call :func:`initialize_from_conf` before any jax API touches a backend.
On TPU pods, all three values can usually be omitted (auto-detected from
the TPU environment). The same machinery runs on CPU processes (used by
the multi-process test), so the multi-host path is testable on one
machine without a pod.

Caveats worth knowing (multi-controller JAX semantics):

* every process must execute the same jitted computations in the same
  order;
* host numpy inputs fed through ``device_put`` with a global
  ``NamedSharding`` must be identical on all processes (they are here:
  graph, targets, and routed query batches are deterministic functions of
  shared inputs);
* pulling a globally-sharded result back to one host needs an allgather —
  use :func:`gather_to_host`.
"""

from __future__ import annotations

from ..utils.env import env_str
from ..utils.log import get_logger

log = get_logger(__name__)


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_ids=None,
               cpu_devices_per_process: int | None = None) -> None:
    """Thin, idempotent wrapper over ``jax.distributed.initialize``.

    ``cpu_devices_per_process``: for CPU-backed multi-process runs (tests,
    pods-without-TPUs) force the CPU platform with that many virtual
    devices and gloo collectives — must be called before any backend
    initializes.
    """
    import jax

    if getattr(initialize, "_done", False):
        return
    if cpu_devices_per_process is not None:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices",
                          int(cpu_devices_per_process))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = int(num_processes)
    if process_id is not None:
        kwargs["process_id"] = int(process_id)
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)
    initialize._done = True  # type: ignore[attr-defined]
    log.info("multihost: process %d/%d up, %d global devices",
             jax.process_index(), jax.process_count(), len(jax.devices()))


def initialize_from_conf(conf) -> bool:
    """Initialize from a ClusterConfig-style object / dict. Returns True
    when multi-host mode was configured. ``process_id`` comes from the
    conf, ``$DOS_PROCESS_ID``, or TPU auto-detection, in that order."""
    mh = getattr(conf, "multihost", None)
    if mh is None and isinstance(conf, dict):
        mh = conf.get("multihost")
    if not mh:
        return False
    pid = mh.get("process_id", env_str("DOS_PROCESS_ID"))
    cpus = mh.get("cpu_devices_per_process")  # CPU-backed pods / tests
    initialize(coordinator=mh.get("coordinator"),
               num_processes=mh.get("num_processes"),
               process_id=None if pid is None else int(pid),
               cpu_devices_per_process=None if cpus is None else int(cpus))
    return True


def _runtime_active() -> bool:
    """True when a multi-controller JAX runtime is up — via this wrapper
    or initialized outside it (direct ``jax.distributed.initialize``, TPU
    pod auto-init). Never triggers backend init itself."""
    if getattr(initialize, "_done", False):
        return True
    try:
        from jax._src import distributed as _jdist

        return _jdist.global_state.client is not None
    except (ImportError, AttributeError):  # private API moved: assume
        return False                       # single-controller


def process_info() -> tuple[int, int]:
    """``(process_index, process_count)`` — ``(0, 1)`` on any
    single-controller run (same guard rationale as :func:`is_primary`)."""
    if _runtime_active():
        import jax

        return jax.process_index(), jax.process_count()
    return 0, 1


def barrier(name: str) -> None:
    """Cross-process rendezvous (no-op single-controller): every process
    must reach it before any proceeds — e.g. all block files written
    before one process writes the index manifest."""
    if _runtime_active():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def is_primary() -> bool:
    """True on the process that should write shared artifacts (process 0),
    and on any single-controller run. Only consults the JAX process index
    when multi-host mode was actually initialized — a run that never
    configured ``multihost`` is always primary (a stray ``$DOS_PROCESS_ID``
    in the shell must not silently suppress campaign output)."""
    return process_info()[0] == 0


def gather_to_host(x):
    """Allgather a globally-sharded array to replicated numpy on every
    process (wraps ``multihost_utils.process_allgather``)."""
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x, tiled=True)
