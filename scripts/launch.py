#!/usr/bin/env python
"""Multi-process launcher — the ``scripts/launch.sh`` analogue.

Reference (``scripts/launch.sh``): a torchrun wrapper that autodetects
NICs, sets the rendezvous endpoint and cluster env, then launches one
process per GPU. The TPU-native contract is one process PER HOST over
``jax.distributed.initialize`` (``utils/distributed.py:97``
``initialize_distributed`` reads COORDINATOR_ADDRESS / NUM_PROCESSES /
PROCESS_ID), so this launcher covers the two bring-up shapes:

- **Localhost simulation** (default): spawn ``--nproc`` processes on
  this machine, each seeing ``--devices-per-proc`` virtual CPU devices
  — the multi-HOST analogue of the CPU test mesh (conftest.py forces
  8 devices in ONE process; this forces N processes × M devices with a
  real coordination service and cross-process collectives). Used by
  ``tests/test_multihost.py``.
- **Pod member** (``--pod``): don't spawn anything; export the env
  contract from the pod runtime's own variables and exec the script.
  On Cloud TPU VMs, MEGASCALE/TPU env vars already carry host identity
  — ``jax.distributed.initialize()`` with no arguments autodetects
  them — so ``--pod`` is only needed when driving a hand-rolled
  cluster (e.g. ssh loops), where you pass --coordinator/--nproc/--rank
  explicitly. See docs/build.md for the v5p pod recipe.

Examples:
  # 2 hosts x 4 devices on localhost, run an SPMD script:
  python scripts/launch.py --nproc 2 --devices-per-proc 4 my_script.py

  # member 1 of a hand-rolled 2-host cluster:
  python scripts/launch.py --pod --coordinator 10.0.0.1:8476 \
      --nproc 2 --rank 1 my_script.py
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--nproc", type=int, default=2,
                    help="number of processes (hosts)")
    ap.add_argument("--devices-per-proc", type=int, default=4,
                    help="virtual CPU devices per process (localhost "
                         "mode; ignored on real TPU hosts)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0's coordination service "
                         "(default: 127.0.0.1:<free port>)")
    ap.add_argument("--rank", type=int, default=None,
                    help="with --pod: this member's process id")
    ap.add_argument("--pod", action="store_true",
                    help="pod-member mode: export env and exec the "
                         "script in-place instead of spawning")
    ap.add_argument("--cpu", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="force the CPU backend in children (--no-cpu "
                         "keeps the host's accelerator backend). A "
                         "chip belongs to one process, and every "
                         "spawned child would see every local chip, so "
                         "--no-cpu is refused with --nproc > 1: one "
                         "process drives all chips of a host (use "
                         "--pod, one member per host, across hosts)")
    ap.add_argument("script", help="python script to run")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    if args.pod:
        if args.rank is None or args.coordinator is None:
            ap.error("--pod requires --coordinator and --rank")
        env = dict(os.environ,
                   COORDINATOR_ADDRESS=args.coordinator,
                   NUM_PROCESSES=str(args.nproc),
                   PROCESS_ID=str(args.rank))
        os.execvpe(sys.executable,
                   [sys.executable, args.script] + args.args, env)

    if not args.cpu and args.nproc > 1:
        ap.error("--no-cpu with --nproc > 1 would start several "
                 "processes on the same local chips; a chip belongs to "
                 "one process. Run one process per host (it drives every "
                 "local chip), or --pod across hosts.")

    coord = args.coordinator or f"127.0.0.1:{_free_port()}"
    procs = []
    for rank in range(args.nproc):
        env = dict(os.environ,
                   COORDINATOR_ADDRESS=coord,
                   NUM_PROCESSES=str(args.nproc),
                   PROCESS_ID=str(rank))
        if args.cpu:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count="
                                f"{args.devices_per_proc}")
        procs.append(subprocess.Popen(
            [sys.executable, args.script] + args.args, env=env))

    rc = 0
    try:
        for p in procs:
            rc = p.wait() or rc
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        rc = 130
    return rc


if __name__ == "__main__":
    sys.exit(main())
