"""Multi-process / multi-host job launcher.

    python -m paddle_tpu.distributed.launch [--ips ip1,ip2] \
        [--nproc_per_node N] [--started_port P] [--log_dir dir] \
        train.py [script args...]

TPU-native equivalent of the reference collective launcher
(/root/reference/python/paddle/distributed/fleet/launch.py:183
`launch_collective`): builds the Cluster/Pod topology (from the TPU pod
env when present, else --ips/localhost), exports the PADDLE_* +
coordinator env to each local worker, spawns them, and propagates the
first failure.  There is no PS mode: parameter-server strategies are out
of TPU scope (SURVEY.md §2.9 #13-15); collective is the only mode.

One process per host owns all of that host's chips (a chip belongs to
one process at a time), so on a TPU host `--nproc_per_node` above 1 is
refused; shard over the local chips with a mesh inside the one worker.
"""

from __future__ import annotations

import argparse
import os
import sys

from .launch_utils import (find_free_ports, get_cluster,
                           get_cluster_from_tpu_env, on_tpu_host,
                           start_local_trainers, watch_local_trainers)


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="paddle_tpu collective launcher")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated host ips (rank order)")
    p.add_argument("--node_ip", type=str, default=None,
                   help="this node's ip (default: first of --ips)")
    p.add_argument("--nproc_per_node", type=int, default=None,
                   help="worker processes per node (default: 1 — one "
                        "process per host owns all its chips; above 1 "
                        "is refused on a TPU host and meant for "
                        "CPU-mesh testing)")
    p.add_argument("--started_port", type=int, default=None)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch_collective(args):
    nproc = args.nproc_per_node or 1
    if nproc > 1 and on_tpu_host():
        sys.exit(
            f"paddle_tpu.distributed.launch: --nproc_per_node {nproc} "
            "refused on a TPU host: one process per host owns all its "
            "chips, and a chip belongs to one process at a time, so "
            f"{nproc} workers would each claim every chip and fail or "
            "hang.  Start one worker per host (the default) and shard "
            "over its chips with a mesh; set JAX_PLATFORMS=cpu to run "
            "several workers on the CPU mesh.")
    topo = get_cluster_from_tpu_env(nproc)
    if topo is not None:
        cluster, pod = topo
    else:
        ips = [s.strip() for s in args.ips.split(",") if s.strip()]
        node_ip = args.node_ip or ips[0]
        if args.started_port:
            port = args.started_port
        elif len(ips) == 1:
            # single-node: reserve genuinely free ports so concurrent
            # jobs on one host don't collide on a fixed base
            port = find_free_ports(nproc)
        else:
            port = 8476  # multi-node needs a pre-agreed base port
        cluster, pod = get_cluster(ips, node_ip, port, nproc)

    cmd = [sys.executable, "-u", args.training_script] \
        + args.training_script_args
    procs = start_local_trainers(cluster, pod, cmd, log_dir=args.log_dir)
    rc = watch_local_trainers(procs)
    if rc != 0:
        sys.exit(rc)


def main(argv=None):
    launch_collective(_parse_args(argv))


if __name__ == "__main__":
    main()
