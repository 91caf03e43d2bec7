"""Per-node process spawner (reference deepspeed/launcher/launch.py:133).

Invoked on every node by the runner (or directly for single-node jobs):

    python -m deepspeed_tpu.launcher.launch \
        --nnodes 2 --node_rank 0 --nproc_per_node 1 \
        --master_addr 10.0.0.1 --master_port 29500 \
        train.py --my-args ...

Spawns ``nproc_per_node`` worker processes with the rendezvous env set
(``DS_TPU_*`` consumed by ``deepspeed_tpu.comm.init_distributed``, plus the
conventional RANK/LOCAL_RANK/WORLD_SIZE), forwards SIGINT/SIGTERM to the
children, and tears the node down if any child dies (reference launch.py:317
signal handling).

On TPU the normal topology is ONE process per host owning all local chips
(``--nproc_per_node 1``); CPU testing can oversubscribe.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from ..utils.compile_cache import ENV_VAR as CACHE_ENV_VAR, default_cache_dir
from ..utils.logging import logger


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="deepspeed_tpu per-node launcher")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master_addr", type=str, default="127.0.0.1")
    p.add_argument("--master_port", type=int, default=29500)
    p.add_argument("--module", action="store_true",
                   help="run the script as a python module (python -m)")
    p.add_argument("--no_python", action="store_true",
                   help="run the script directly without the python interpreter")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def build_child_env(base_env: dict, args, local_rank: int) -> dict:
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    env = dict(base_env)
    env.update({
        "DS_TPU_COORDINATOR": f"{args.master_addr}:{args.master_port}",
        "DS_TPU_NUM_PROCESSES": str(world),
        "DS_TPU_PROCESS_ID": str(rank),
        # conventional names for user scripts / tooling
        "RANK": str(rank),
        "LOCAL_RANK": str(local_rank),
        "WORLD_SIZE": str(world),
        "MASTER_ADDR": args.master_addr,
        "MASTER_PORT": str(args.master_port),
    })
    if world == 1:
        # single process needs no rendezvous; don't force jax.distributed
        env.pop("DS_TPU_COORDINATOR")
    # the child is the user's own script: it gets the fixed compile-cache
    # directory through the variable jax reads itself, unless the caller
    # has already placed the cache (utils/compile_cache.py)
    env.setdefault(CACHE_ENV_VAR, default_cache_dir())
    return env


def main(argv=None) -> int:
    args = parse_args(argv)
    script_args = list(args.training_script_args)
    if script_args and script_args[0] == "--":
        script_args = script_args[1:]

    procs: list[subprocess.Popen] = []
    for local_rank in range(args.nproc_per_node):
        env = build_child_env(os.environ, args, local_rank)
        if args.no_python:
            cmd = [args.training_script]
        elif args.module:
            cmd = [sys.executable, "-u", "-m", args.training_script]
        else:
            cmd = [sys.executable, "-u", args.training_script]
        cmd += script_args
        logger.info(f"launch: node_rank={args.node_rank} local_rank={local_rank} "
                    f"rank={env.get('RANK')} cmd={' '.join(cmd)}")
        procs.append(subprocess.Popen(cmd, env=env))

    # forward signals so ^C / scheduler preemption reaches every worker
    def _forward(signum, frame):
        logger.warning(f"launch: forwarding signal {signum} to {len(procs)} workers")
        for p in procs:
            if p.poll() is None:
                p.send_signal(signum)

    signal.signal(signal.SIGINT, _forward)
    signal.signal(signal.SIGTERM, _forward)

    # monitor: first failure tears down the node (reference launch.py:317)
    exit_code = 0
    alive = set(range(len(procs)))
    while alive:
        time.sleep(0.2)
        for i in sorted(alive):
            rc = procs[i].poll()
            if rc is None:
                continue
            alive.discard(i)
            if rc != 0:
                exit_code = rc
                logger.error(f"launch: worker local_rank={i} failed rc={rc}; "
                             f"terminating peers")
                for j in sorted(alive):
                    procs[j].terminate()
                deadline = time.time() + 10
                for j in sorted(alive):
                    try:
                        procs[j].wait(timeout=max(0.1, deadline - time.time()))
                    except subprocess.TimeoutExpired:
                        procs[j].kill()
                alive.clear()
                break
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
