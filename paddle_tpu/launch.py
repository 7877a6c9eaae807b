"""Multi-process launcher.

Reference parity: /root/reference/python/paddle/distributed/launch.py:132
(spawns one trainer process per device/node slot with
PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS /
PADDLE_CURRENT_ENDPOINT injected; trainers bootstrap NCCL from these).

TPU-first difference: within one host, SPMD needs ONE process driving all
local chips, so --nproc_per_node defaults to 1 and the launcher's main job
is multi-HOST fan-out: every spawned process gets the same env contract and
fleet.init() wires jax.distributed from it.  With more than one process per
host the first to touch JAX holds every local chip and the others fail or
hang at start-up: only use it where each process is pinned to its own chips
(TPU_VISIBLE_CHIPS and the matching TPU_PROCESS_* variables, set by the
caller) or runs on the CPU.  This launcher itself never imports JAX.

Usage:  python -m paddle_tpu.launch --nnodes 1 --node_rank 0 \
            --started_port 6170 train.py [args...]
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.launch")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per node; keep 1 per TPU host")
    p.add_argument("--node_ips", type=str, default="127.0.0.1",
                   help="comma-separated node ips")
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch(args):
    ips = args.node_ips.split(",")
    nproc = args.nproc_per_node
    endpoints = []
    for ip in ips:
        for i in range(nproc):
            endpoints.append(f"{ip}:{args.started_port + i}")
    world = args.nnodes * nproc

    procs = []
    for local in range(nproc):
        rank = args.node_rank * nproc + local
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_COORDINATOR_ENDPOINT": endpoints[0],
            "FLAGS_selected_gpus": str(local),   # reference-compat
        })
        cmd = [sys.executable, args.training_script] \
            + args.training_script_args
        procs.append(subprocess.Popen(cmd, env=env))

    def _terminate(sig, frame):
        for pr in procs:
            pr.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    code = 0
    for pr in procs:
        pr.wait()
        if pr.returncode != 0:
            code = pr.returncode
    return code


def main(argv=None):
    args = _parse_args(argv)
    sys.exit(launch(args))


if __name__ == "__main__":
    main()
