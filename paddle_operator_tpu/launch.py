"""In-pod bootstrap: ``python -m paddle_operator_tpu.launch train.py``.

The TPU-native replacement for ``python -m paddle.distributed.launch``
(reference example: ``deploy/examples/resnet.yaml:12-17``): reads the env the
operator injected (``TPU_WORKER_ID`` per-pod + ``TPU_WORKER_HOSTNAMES``/
``TPUJOB_COORDINATOR`` from the ConfigMap barrier, with ``PADDLE_*`` names
accepted for CPU/PS parity), brings up ``jax.distributed`` so every host
joins the same XLA world, and — for elastic jobs — runs the membership agent
that watches the np/epoch keys (reference protocol:
``paddle.distributed.launch --elastic_server`` watching etcd, SURVEY.md §3.4)
and restarts training from the newest checkpoint on a membership epoch bump.
"""

from __future__ import annotations

import os
import runpy
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from .elastic.store import KVStore, connect as kv_connect
from .elastic.sync import epoch_key, np_key


@dataclass
class LaunchConfig:
    worker_id: int = 0             # GLOBAL rank across all slices
    num_workers: int = 1           # total hosts across all slices
    coordinator: str = ""          # host:port of slice-0 worker-0
    slice_id: int = 0              # multislice: which ICI domain this host is in
    num_slices: int = 1            # multislice: DCN-connected slice count
    hostnames: List[str] = field(default_factory=list)
    role: str = "TRAINER"
    # PS mode (operator env PADDLE_PSERVERS_IP_PORT_LIST): host:port of
    # every parameter server; consumed by ps.run_ps_training
    ps_endpoints: List[str] = field(default_factory=list)
    job_id: str = ""
    elastic_server: str = ""
    elastic_timeout: float = 60.0
    checkpoint_dir: str = os.environ.get("TPUJOB_CHECKPOINT_DIR", "/checkpoint")

    @property
    def is_distributed(self) -> bool:
        return self.num_workers > 1

    @property
    def is_elastic(self) -> bool:
        return bool(self.elastic_server)


def _env(*names: str, default: str = "") -> str:
    for n in names:
        v = os.environ.get(n)
        if v:
            return v
    return default


def detect_env(environ: Optional[dict] = None) -> LaunchConfig:
    """Build a LaunchConfig from operator-injected env (TPU names first,
    PADDLE_* parity names second)."""
    if environ is not None:
        saved = os.environ
        os.environ = environ  # type: ignore[assignment]
    try:
        hostnames_s = _env("TPU_WORKER_HOSTNAMES")
        hostnames = [h for h in hostnames_s.split(",") if h] if hostnames_s else []
        if not hostnames:
            eps = _env("PADDLE_TRAINER_ENDPOINTS")
            hostnames = [e.split(":")[0] for e in eps.split(",") if e]

        # Multislice: TPU_WORKER_HOSTNAMES / TPU_WORKER_ID are slice-local
        # (the TPU runtime's view); TPUJOB_* are the global world
        # jax.distributed needs. When only MEGASCALE_* + slice-local env is
        # present (e.g. GKE-native injection), scale the fallbacks by the
        # slice count instead of silently rendezvousing per-slice worlds.
        num_slices = int(_env("MEGASCALE_NUM_SLICES", default="1"))
        slice_id = int(_env("MEGASCALE_SLICE_ID", default="0"))
        hosts_per_slice = max(len(hostnames), 1)
        num_workers = int(
            _env("TPUJOB_NUM_WORKERS", "PADDLE_TRAINERS_NUM", default="0")
        ) or hosts_per_slice * num_slices

        coordinator = _env("TPUJOB_COORDINATOR")
        if not coordinator:
            port = _env("PADDLE_PORT", default="2379")
            host = ""
            if num_slices > 1:
                # slice-local hostnames[0] is the wrong host on slices > 0;
                # the MEGASCALE coordinator lives on slice 0. With neither
                # source present, fail fast — falling back to the slice-local
                # list would rendezvous divergent per-slice worlds that hang
                # in jax.distributed.initialize with no error.
                mca = _env("MEGASCALE_COORDINATOR_ADDRESS")
                if not mca:
                    raise RuntimeError(
                        "multislice launch needs TPUJOB_COORDINATOR or "
                        "MEGASCALE_COORDINATOR_ADDRESS; slice-local hostnames "
                        "cannot name the slice-0 coordinator"
                    )
                host = mca.split(":")[0]
            if not host and hostnames:
                host = hostnames[0]
            if host:
                coordinator = "%s:%s" % (host, port)

        worker_id_s = _env("TPUJOB_WORKER_ID", "PADDLE_TRAINER_ID")
        if worker_id_s:
            worker_id = int(worker_id_s)
        else:
            worker_id = int(_env("TPU_WORKER_ID", default="0"))
            if num_slices > 1:
                worker_id += slice_id * hosts_per_slice
        return LaunchConfig(
            worker_id=worker_id,
            num_workers=num_workers,
            coordinator=coordinator,
            slice_id=slice_id,
            num_slices=num_slices,
            hostnames=hostnames,
            role=_env("TRAINING_ROLE", default="TRAINER"),
            ps_endpoints=[
                e for e in _env("PADDLE_PSERVERS_IP_PORT_LIST").split(",")
                if e],
            job_id=_env("PADDLE_ELASTIC_JOB_ID", "TPUJOB_JOB_ID"),
            elastic_server=_env("TPUJOB_ELASTIC_SERVER", "PADDLE_ELASTIC_SERVER"),
            elastic_timeout=float(_env("PADDLE_ELASTIC_TIMEOUT", default="60")),
        )
    finally:
        if environ is not None:
            os.environ = saved  # type: ignore[assignment]


def initialize_distributed(cfg: LaunchConfig) -> None:
    """jax.distributed.initialize with the operator-provided world view.

    All hosts must call this with identical (coordinator, num_processes) —
    guaranteed by the ConfigMap barrier: the env only materializes once every
    pod has an IP (reference mechanism: paddlejob_controller.go:289-306).
    """
    if not cfg.is_distributed:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_workers,
        process_id=cfg.worker_id,
    )


class ElasticAgent:
    """Watches membership np/epoch; drives restart-from-checkpoint cycles.

    Protocol (operator side in elastic/sync.py): the controller writes the
    desired world size to ``np`` and bumps ``epoch`` whenever it changes.
    Workers poll; when the epoch moves past the one they trained under, the
    current training run is asked to stop (via the ``should_stop`` callable
    handed to ``train_fn``), the agent re-reads the world, and calls
    ``train_fn`` again — which resumes from the newest checkpoint.
    """

    def __init__(self, cfg: LaunchConfig, store: Optional[KVStore] = None,
                 poll_interval: float = 2.0):
        self.cfg = cfg
        self.store = store or kv_connect(cfg.elastic_server.split(",")[0])
        self.poll_interval = poll_interval
        ns_name = cfg.job_id or "default-job"
        if "-" in ns_name:
            ns, _, name = ns_name.partition("-")
        else:
            ns, name = "default", ns_name
        self._np_key = np_key(ns, name)
        self._epoch_key = epoch_key(ns, name)

    def read_world(self):
        np_v = self.store.get(self._np_key)
        epoch_v = self.store.get(self._epoch_key)
        return (int(np_v) if np_v else self.cfg.num_workers,
                int(epoch_v) if epoch_v else 0)

    def run(self, train_fn: Callable, max_cycles: int = 0) -> int:
        """Run train cycles until training reports completion.

        ``train_fn(world_size, epoch, should_stop) -> bool`` returns True when
        training is COMPLETE (not merely interrupted). ``should_stop()`` is
        cheap and poll-safe for the inner loop. Returns cycles executed.
        """
        cycles = 0
        while True:
            world, epoch = self.read_world()
            self._last_poll = 0.0

            def should_stop() -> bool:
                now = time.monotonic()
                if now - self._last_poll < self.poll_interval:
                    return False
                self._last_poll = now
                _, cur = self.read_world()
                return cur != epoch

            done = train_fn(world, epoch, should_stop)
            cycles += 1
            if done:
                return cycles
            if max_cycles and cycles >= max_cycles:
                return cycles


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print("usage: python -m paddle_operator_tpu.launch SCRIPT [args...]",
              file=sys.stderr)
        return 2
    cfg = detect_env()
    print(
        "[tpujob.launch] worker %d/%d coordinator=%s elastic=%s"
        % (cfg.worker_id, cfg.num_workers, cfg.coordinator or "-",
           cfg.elastic_server or "-"),
        flush=True,
    )
    initialize_distributed(cfg)
    import jax

    # which backend this job really got, before it spends an hour on it:
    # with no platform pinned JAX takes the CPU when it finds no chip
    dev = jax.devices()[0]
    print(
        "[tpujob.launch] platform=%s device_kind=%s devices=%d"
        % (dev.platform, dev.device_kind, len(jax.devices())),
        flush=True,
    )
    script, sys.argv = argv[0], argv
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
