#!/usr/bin/env python
"""Distributed launcher (parity: the reference's ``tools/launch.py`` +
dmlc-core tracker — SURVEY.md §3.4).

The reference starts scheduler/server/worker processes over ssh/yarn/...
for the ps-lite parameter server. The TPU-native substitute is SPMD:
every process is a WORKER running the same program; coordination is
``jax.distributed.initialize`` (one coordinator, N processes) and
parameter sync is XLA collectives over ICI/DCN — no scheduler or server
roles exist (SURVEY.md §3.4 "TPU translation").

Supported launchers:
  local  — fork N worker processes on this host (the reference's CI idiom
           for testing dist kvstore without a cluster; SURVEY.md §4
           idiom 4). Sets JAX_COORDINATOR_ADDRESS / JAX_PROCESS_ID /
           JAX_NUM_PROCESSES plus the DMLC_* names scripts may read.
           This is the CPU multi-process TEST launcher (run it with
           JAX_PLATFORMS=cpu): on a host that holds chips every worker
           would claim all of them, and a chip belongs to one process —
           there, one process drives all local chips through a mesh.
  ssh    — print the per-host commands (zero-egress build: execution via
           ssh is left to the operator / real cluster tooling).

Example:
  python tools/launch.py -n 4 --launcher local python train.py \
      --kvstore dist_sync
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(rank, n, coord, extra=None):
    env = dict(os.environ)
    env.update({
        # JAX multi-process bootstrap (jax.distributed.initialize reads
        # these when called with no args)
        "JAX_COORDINATOR_ADDRESS": coord,
        "JAX_PROCESS_ID": str(rank),
        "JAX_NUM_PROCESSES": str(n),
        # reference-compatible names (scripts written against the
        # reference's tracker keep working)
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(n),
        "DMLC_NUM_SERVER": "0",
        "DMLC_WORKER_ID": str(rank),
        "MXTPU_COORDINATOR": coord,
        "MXTPU_NUM_PROCS": str(n),
        "MXTPU_PROC_ID": str(rank),
    })
    if extra:
        env.update(extra)
    return env


def launch_local(n: int, command, port=None) -> int:
    """Fork n workers on this host; returns the first nonzero exit code
    (0 when all succeed). CPU test launcher — see the module docstring
    for why not on a host with chips."""
    coord = f"127.0.0.1:{port or _free_port()}"
    procs = []
    for rank in range(n):
        procs.append(subprocess.Popen(
            command, env=_worker_env(rank, n, coord)))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch a distributed SPMD job "
                    "(reference tools/launch.py parity)")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for parity; SPMD has no server role")
    ap.add_argument("--launcher", choices=("local", "ssh"),
                    default="local")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="one host per line (ssh launcher)")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        ap.error("no command given")
    if args.num_servers:
        print("note: SPMD has no parameter-server processes; "
              "-s is ignored (optimizer runs data-parallel in-step)",
              file=sys.stderr)

    if args.launcher == "local":
        return launch_local(args.num_workers, args.command, args.port)

    # ssh: emit the exact command per host (zero-egress environment)
    hosts = []
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < args.num_workers:
        hosts += ["<host%d>" % i for i in range(len(hosts),
                                                args.num_workers)]
    coord = f"{hosts[0]}:{args.port or 9876}"
    cmd = " ".join(args.command)
    for rank in range(args.num_workers):
        env = (f"JAX_COORDINATOR_ADDRESS={coord} JAX_PROCESS_ID={rank} "
               f"JAX_NUM_PROCESSES={args.num_workers} DMLC_ROLE=worker")
        print(f"ssh {hosts[rank]} '{env} {cmd}'")
    return 0


if __name__ == "__main__":
    sys.exit(main())
