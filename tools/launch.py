#!/usr/bin/env python
"""Cluster launcher (reference tools/launch.py:71-121, which delegates to
dmlc_tracker's ssh/mpi/sge/yarn/local modes and wires the DMLC_* env
protocol for ps-lite).

TPU-native redesign: there is no scheduler/server role — every process is a
peer in the jax distributed runtime. The launcher starts N worker processes
(locally or over ssh), giving each the JAX coordination env:

    JAX_COORDINATOR_ADDRESS  host:port of process 0
    JAX_NUM_PROCESSES        n
    JAX_PROCESS_ID           0..n-1

plus the framework's own MXTPU_* mirrors, then waits. ONE PROCESS PER HOST:
a host's chips are driven by one process over a mesh (a chip belongs to one
process at a time), and `--launcher local` starts its workers with identical
device visibility — on a multi-chip TPU host every worker would claim every
chip — so on accelerator hosts use it with -n 1 per host (or on the CPU).
Inside the program,
`incubator_mxnet_tpu.kvstore.create("tpu")` picks rank/size from the jax
runtime, so reference-style `launch.py -n 4 python train.py --kv-store tpu`
keeps its shape.

Usage:
    python tools/launch.py -n 8 -H hostfile --launcher ssh python train.py
    JAX_PLATFORMS=cpu python tools/launch.py -n 4 python train_mnist.py \
        --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def parse_hostfile(path):
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                hosts.append(line.split()[0])
    if not hosts:
        raise SystemExit(f"hostfile {path} has no hosts")
    return hosts


def worker_env(base, i, n, coordinator):
    env = dict(base)
    env.update({
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(n),
        "JAX_PROCESS_ID": str(i),
        "MXTPU_NUM_WORKERS": str(n),
        "MXTPU_WORKER_ID": str(i),
        # reference protocol mirrors so ported scripts reading DMLC_* work
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(i),
        "DMLC_ROLE": "worker",
    })
    return env


def launch_local(n, cmd, coordinator):
    procs = []
    try:
        for i in range(n):
            procs.append(subprocess.Popen(
                cmd, env=worker_env(os.environ, i, n, coordinator)))
        rc = 0
        for p in procs:
            rc = p.wait() or rc
        return rc
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        for p in procs:
            p.wait()
        return 130


def launch_ssh(n, hosts, cmd, coordinator, user=None):
    """One worker per host round-robin; assumes passwordless ssh + synced
    working directory (same contract as the reference's ssh tracker)."""
    procs = []
    cwd = os.getcwd()
    for i in range(n):
        host = hosts[i % len(hosts)]
        target = f"{user}@{host}" if user else host
        envs = " ".join(f"{k}={v!r}" for k, v in
                        worker_env({}, i, n, coordinator).items())
        remote = f"cd {cwd} && env {envs} " + " ".join(cmd)
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no",
                                       target, remote]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--coordinator", default="127.0.0.1:43219",
                    help="host:port of process 0's coordination service")
    ap.add_argument("--user", default=None, help="ssh user")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    cmd = args.command[1:] if args.command[0] == "--" else args.command

    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh requires -H hostfile")
        hosts = parse_hostfile(args.hostfile)
        coord = args.coordinator
        if coord.startswith("127."):
            coord = f"{hosts[0]}:{coord.rsplit(':', 1)[1]}"
        return launch_ssh(args.num_workers, hosts, cmd, coord, args.user)
    return launch_local(args.num_workers, cmd, args.coordinator)


if __name__ == "__main__":
    sys.exit(main())
