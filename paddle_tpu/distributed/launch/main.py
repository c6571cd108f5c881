"""Process launcher CLI (reference: python/paddle/distributed/launch/main.py:18,
CollectiveController run loop launch/controllers/collective.py:268,
HTTPMaster rendezvous controllers/master.py:73).

Usage:  python -m paddle_tpu.distributed.launch [--nnodes N] [--node_rank R]
            [--nproc_per_node P] [--master HOST:PORT] [--log_dir DIR]
            [--elastic_level L] [--max_restarts K] training_script [args...]

TPU-native notes: a TPU host runs ONE process owning all local chips
(nproc_per_node=1 default). nproc_per_node > 1 — the reference's per-GPU
process model — is CPU-only for now: a chip belongs to one process at a
time, the children inherit this environment (``--devices`` hands every
child the SAME list), so on a TPU host each would claim every chip. This
parent never touches JAX itself. The
rank-0 TCP store (native C++ TCPStore) plays the HTTPMaster role; each
child gets the reference env contract (PADDLE_TRAINER_ID,
PADDLE_TRAINER_ENDPOINTS, MASTER_ADDR/PORT, PADDLE_NNODES).
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "main"]

ELASTIC_EXIT_CODE = 101  # reference fleet/elastic/manager.py:30


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="multi-process / multi-node launcher")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""))
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--elastic_level", type=int, default=0)
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--devices", type=str, default=None,
                   help="visible device ids for this node")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _child_env(args, local_rank: int, generation: int = 0) -> dict:
    env = dict(os.environ)
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    master = args.master or "127.0.0.1:0"
    host, _, port = master.partition(":")
    env.update({
        # restart generation: ElasticManager scopes its store keys by this
        # so a relaunched world starts from clean membership counters
        "PADDLE_ELASTIC_GENERATION": str(generation),
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_NNODES": str(args.nnodes),
        "PADDLE_TRAINER_ENDPOINTS": master,
        "MASTER_ADDR": host or "127.0.0.1",
        "MASTER_PORT": port or "0",
    })
    if args.devices:
        env["CUDA_VISIBLE_DEVICES"] = args.devices
        env["TPU_VISIBLE_DEVICES"] = args.devices
    return env


def launch(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    os.makedirs(args.log_dir, exist_ok=True)
    restarts = 0
    while True:
        procs: List[subprocess.Popen] = []
        logs = []
        for lr in range(args.nproc_per_node):
            rank = args.node_rank * args.nproc_per_node + lr
            log = open(os.path.join(args.log_dir,
                                    f"workerlog.{rank}"), "ab")
            logs.append(log)
            cmd = [sys.executable, args.script] + args.script_args
            procs.append(subprocess.Popen(
                cmd, env=_child_env(args, lr, generation=restarts),
                stdout=log, stderr=log))

        # watch loop (≙ CollectiveController.run :268)
        fail_code = 0
        try:
            while procs:
                alive = []
                for p in procs:
                    rc = p.poll()
                    if rc is None:
                        alive.append(p)
                    elif rc != 0:
                        fail_code = rc
                        break
                if fail_code:
                    break
                if not alive:
                    break
                procs = alive
                time.sleep(0.2)
        except KeyboardInterrupt:
            fail_code = -signal.SIGINT
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            for log in logs:
                log.close()

        if fail_code == 0:
            return 0
        if (args.elastic_level > 0 and restarts < args.max_restarts
                and fail_code in (ELASTIC_EXIT_CODE, 1)):
            restarts += 1
            print(f"[launch] child failed (code {fail_code}); restart "
                  f"{restarts}/{args.max_restarts}", file=sys.stderr)
            continue
        return int(fail_code) if fail_code > 0 else 1


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
