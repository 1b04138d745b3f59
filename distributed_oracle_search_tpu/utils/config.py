"""Cluster configuration.

Schema parity with the reference cluster-conf JSON
(``example-cluster-conf.json:1-11``, documented in reference ``README.md:27-39``):

* ``workers``     list of worker identities. For host-backed execution these
                  are ssh hostnames (reference semantics); for TPU-backed
                  execution use ``partmethod: "tpu"`` and the list length is
                  simply the number of mesh shards (entries may be anything,
                  conventionally ``"tpu:<i>"``).
* ``nfs``         shared scratch directory for query files (host mode only).
* ``projectdir``  working dir used after ssh-ing to a worker (host mode only).
* ``partmethod``  ``div | mod | alloc | tpu`` — how nodes map to workers.
* ``partkey``     integer parameter of the partition method (``alloc`` takes a
                  list of range bounds; ``tpu`` ignores it and derives a
                  contiguous chunking from the node count).
* ``outdir``      directory holding the precomputed CPD index.
* ``xy_file``     input graph path.
* ``scenfile``    query scenario path.
* ``diffs``       list of congestion diff files ("-" = free flow).

New (this framework): ``partmethod: "tpu"`` routes partitions onto a
``jax.sharding.Mesh`` in-process instead of onto ssh hostnames — the north-star
design from BASELINE.json. ``mesh_shape``/``mesh_axes`` optionally pin the mesh
layout (e.g. ``[2, 4]`` with ``["data", "worker"]`` — consumed by
``parallel.mesh.mesh_from_config``, which every TPU-mode entry point uses);
by default a ``(1, maxworker)`` mesh.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Sequence

VALID_PARTMETHODS = ("div", "mod", "alloc", "tpu")


@dataclasses.dataclass
class ClusterConfig:
    workers: list[str]
    partmethod: str = "mod"
    partkey: Any = 1
    outdir: str = "./index"
    xy_file: str = ""
    scenfile: str = ""
    diffs: list[str] = dataclasses.field(default_factory=lambda: ["-"])
    nfs: str = "/tmp"
    projectdir: str = "."
    #: R-way shard replication (host/serving modes): replica rank r of
    #: worker w's rows also lives on worker (w + r) % maxworker, giving
    #: the head failover targets and the frontend hedge targets. 1 =
    #: no replication (today's behavior). ``DOS_REPLICATION`` overrides.
    replication: int = 1
    # TPU-mode extensions (ignored by host mode)
    mesh_shape: Sequence[int] | None = None
    mesh_axes: Sequence[str] | None = None
    # multi-host: {"coordinator": "host:port", "num_processes": N,
    # "process_id": i (or $DOS_PROCESS_ID / TPU auto-detect)} — see
    # parallel/multihost.py
    multihost: dict | None = None

    @property
    def maxworker(self) -> int:
        return len(self.workers)

    def validate(self) -> "ClusterConfig":
        if not self.workers:
            raise ValueError("cluster config needs at least one worker")
        if self.partmethod not in VALID_PARTMETHODS:
            raise ValueError(
                f"partmethod {self.partmethod!r} not in {VALID_PARTMETHODS}")
        if self.partmethod == "alloc":
            if not isinstance(self.partkey, (list, tuple)):
                raise ValueError("alloc partitioning needs a list partkey")
            if len(self.partkey) != self.maxworker:
                raise ValueError("alloc partkey must have one bound per worker")
        elif self.partmethod in ("div", "mod"):
            if not isinstance(self.partkey, int) or self.partkey <= 0:
                raise ValueError(f"{self.partmethod} needs a positive int partkey")
        if (not isinstance(self.replication, int)
                or not 1 <= self.replication <= self.maxworker):
            raise ValueError(
                f"replication must be an int in [1, maxworker="
                f"{self.maxworker}], got {self.replication!r}")
        return self

    def effective_replication(self) -> int:
        """The conf's replication with the ``DOS_REPLICATION`` env
        override applied (env policy: a malformed or out-of-range value
        degrades to the conf's, never crashes)."""
        from .env import env_cast
        from .log import get_logger

        r = env_cast("DOS_REPLICATION", None, int)
        if r is None:
            return self.replication
        if not 1 <= r <= self.maxworker:
            get_logger(__name__).warning(
                "ignoring DOS_REPLICATION=%d outside [1, maxworker=%d]; "
                "using %d", r, self.maxworker, self.replication)
            return self.replication
        return r

    @property
    def is_tpu(self) -> bool:
        return self.partmethod == "tpu"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d = {k: v for k, v in d.items() if v is not None}
        if d.get("replication") == 1:
            del d["replication"]      # R=1 confs stay byte-identical
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ClusterConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known}).validate()

    @classmethod
    def load(cls, path: str) -> "ClusterConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def save(self, path: str) -> None:
        # tmp+fsync+rename: a conf is a durable artifact every worker
        # and campaign reads — never observable torn
        from .atomicio import atomic_write_json
        atomic_write_json(path, self.to_dict())


def test_config(datadir: str = "./data", n_workers: int = 8,
                partmethod: str = "tpu") -> ClusterConfig:
    """Canned smoke-test config.

    Mirrors the reference's ``-t`` mode (``process_query.py:241-256``: 100×
    localhost, mod/100) but defaults to the TPU backend with a shard count
    matched to the local device/virtual-device count.
    """
    if partmethod == "tpu":
        workers = [f"tpu:{i}" for i in range(n_workers)]
        partkey = n_workers
    else:
        workers = ["localhost"] * n_workers
        partkey = n_workers
    return ClusterConfig(
        workers=workers,
        partmethod=partmethod,
        partkey=partkey,
        outdir=os.path.join(datadir, "index"),
        xy_file=os.path.join(datadir, "synth-city.xy"),
        scenfile=os.path.join(datadir, "synth.scen"),
        diffs=[os.path.join(datadir, "synth-city.xy.diff")],
    ).validate()


def test_worker_count(backend: str) -> int:
    """Shards of the canned ``-t`` config: one per local device when the
    campaign runs in-process on the mesh, else 8 host workers. A
    host-backend head never touches JAX: it would hold the chip its
    worker processes need."""
    if backend == "host":
        return 8
    import jax

    return len(jax.devices())
