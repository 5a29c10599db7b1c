"""Cluster/Pod topology + local process management for the launcher.

TPU-native re-design of the reference launcher plumbing
(/root/reference/python/paddle/distributed/fleet/launch_utils.py: Cluster/
Pod/Trainer classes, get_cluster, start_local_trainers, watch_local_
trainers).  Differences by design:

* One worker PROCESS per host is the JAX model (a process owns all local
  chips through one runtime), not one process per device like the
  reference's one-proc-per-GPU.  A chip belongs to one process at a
  time, so `nproc_per_node > 1` on a TPU host — where every worker
  would claim every chip — is refused (`on_tpu_host`); it stays
  available for CPU-mesh testing and host-parallel ingestion.
* Rendezvous is `jax.distributed.initialize` against a coordinator
  address (the rank-0 endpoint) instead of gloo HTTP stores +
  `c_gen_nccl_id` broadcast: the JAX coordination service replaces both.
* TPU pod topology is read from the standard TPU VM env (TPU_WORKER_ID,
  TPU_WORKER_HOSTNAMES) when present, replacing the reference's
  PADDLE_CLUSTER/POD_IP cloud env parsing.
"""

from __future__ import annotations

import glob
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Trainer:
    endpoint: str
    rank: int
    accelerators: List[int] = field(default_factory=list)


@dataclass
class Pod:
    ip: str
    trainers: List[Trainer] = field(default_factory=list)


@dataclass
class Cluster:
    pods: List[Pod] = field(default_factory=list)

    def trainers(self) -> List[Trainer]:
        return [t for p in self.pods for t in p.trainers]

    def endpoints(self) -> List[str]:
        return [t.endpoint for t in self.trainers()]

    def world_size(self) -> int:
        return len(self.trainers())

    def coordinator(self) -> str:
        return self.endpoints()[0]


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _vfio_group_vendors(group: str) -> List[str]:
    """PCI vendor ids of the devices behind /dev/vfio/<group>."""
    vendors = []
    for path in glob.glob(
            f"/sys/kernel/iommu_groups/{group}/devices/*/vendor"):
        with open(path) as f:
            vendors.append(f.read().strip())
    return vendors


def on_tpu_host() -> bool:
    """Whether workers started here would find TPU chips: JAX is not
    pinned to another platform and the host exposes TPU device nodes —
    `/dev/accel<N>` (the TPU driver's own nodes; the kernel's generic
    accelerators sit under `/dev/accel/`), or a VFIO group holding a
    Google PCI device (any passthrough host has `/dev/vfio/<N>`, so the
    node alone says nothing).  Decided without touching JAX — a
    launcher that initialised the runtime would itself hold the chips
    its worker needs."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    if glob.glob("/dev/accel[0-9]*"):
        return True
    return any(_GOOGLE_PCI_VENDOR in _vfio_group_vendors(
        os.path.basename(node)) for node in glob.glob("/dev/vfio/[0-9]*"))


def find_free_ports(n: int) -> List[int]:
    ports, socks = [], []
    try:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def get_cluster(node_ips: List[str], node_ip: str, started_port,
                nproc_per_node: int) -> (Cluster, Pod):
    """Static topology: every node runs `nproc_per_node` workers on
    consecutive ports from `started_port` (the reference's
    get_cluster_from_args contract, so its launch scripts translate).
    `started_port` may also be an explicit port LIST (single-node
    launches pass freshly reserved free ports to avoid collisions
    between concurrent jobs)."""
    ports = (list(started_port) if isinstance(started_port, (list, tuple))
             else [started_port + i for i in range(nproc_per_node)])
    cluster = Cluster()
    rank = 0
    current = None
    for ip in node_ips:
        pod = Pod(ip=ip)
        for i in range(nproc_per_node):
            pod.trainers.append(
                Trainer(endpoint=f"{ip}:{ports[i]}", rank=rank))
            rank += 1
        cluster.pods.append(pod)
        if ip == node_ip:
            current = pod
    if current is None:
        raise ValueError(f"node_ip {node_ip} not in --ips {node_ips}")
    return cluster, current


def get_cluster_from_tpu_env(nproc_per_node: int = 1):
    """TPU pod topology from the TPU VM metadata env.  Returns None when
    not on a TPU pod (caller falls back to --ips/localhost)."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES")
    wid = os.environ.get("TPU_WORKER_ID")
    if not hosts or wid is None:
        return None
    ips = [h.strip() for h in hosts.split(",") if h.strip()]
    port = int(os.environ.get("PADDLE_TPU_PORT", "8476"))
    return get_cluster(ips, ips[int(wid)], port, nproc_per_node)


@dataclass
class TrainerProc:
    proc: subprocess.Popen
    rank: int
    log_fh: Optional[object] = None


def trainer_env(cluster: Cluster, trainer: Trainer,
                extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Per-worker env: the reference's PADDLE_* contract plus the JAX
    coordination address, so both `init_parallel_env()` and raw
    `jax.distributed.initialize()` pick the topology up."""
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(trainer.rank),
        "PADDLE_CURRENT_ENDPOINT": trainer.endpoint,
        "PADDLE_TRAINERS_NUM": str(cluster.world_size()),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(cluster.endpoints()),
        "PADDLE_COORDINATOR": cluster.coordinator(),
    })
    if extra:
        env.update(extra)
    return env


def start_local_trainers(cluster: Cluster, pod: Pod, cmd: List[str],
                         log_dir: Optional[str] = None,
                         extra_env: Optional[Dict[str, str]] = None
                         ) -> List[TrainerProc]:
    procs = []
    for t in pod.trainers:
        env = trainer_env(cluster, t, extra_env)
        fh = None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            fh = open(os.path.join(log_dir, f"workerlog.{t.rank}"), "w")
        p = subprocess.Popen(cmd, env=env, stdout=fh or None,
                             stderr=subprocess.STDOUT if fh else None)
        procs.append(TrainerProc(proc=p, rank=t.rank, log_fh=fh))
    return procs


def terminate_local_trainers(procs: List[TrainerProc]):
    for tp in procs:
        if tp.proc.poll() is None:
            try:
                tp.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + 10
    for tp in procs:
        try:
            tp.proc.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            tp.proc.kill()
    for tp in procs:
        if tp.log_fh:
            tp.log_fh.close()


def watch_local_trainers(procs: List[TrainerProc],
                         poll_s: float = 0.5) -> int:
    """Block until all workers exit.  First non-zero exit terminates the
    rest (the reference's watch_local_trainers failure propagation).
    Returns the first failing rank's code, or 0."""
    try:
        while True:
            alive = False
            for tp in procs:
                rc = tp.proc.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    terminate_local_trainers(procs)
                    return rc
            if not alive:
                return 0
            time.sleep(poll_s)
    except KeyboardInterrupt:
        terminate_local_trainers(procs)
        raise
