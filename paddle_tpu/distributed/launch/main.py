"""`python -m paddle_tpu.distributed.launch [--nproc_per_node N] script.py args...`

Single-host multi-process launcher (reference launch/main.py +
controllers/collective.py: per-rank PADDLE_TRAINER_ID / endpoints env,
log files per rank, tail-on-failure job/container.py behavior).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch_main"]


def _parse():
    p = argparse.ArgumentParser(prog="paddle_tpu.distributed.launch")
    p.add_argument("--nproc_per_node", "--nprocs", type=int, default=1)
    p.add_argument("--master", default="127.0.0.1:23571",
                   help="coordinator host:port (rank0)")
    p.add_argument("--rank", default="0",
                   help="this host's index, or 'auto' to rendezvous "
                        "through the master TCPStore (reference "
                        "launch/controllers/master.py HTTP/etcd rendezvous)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--max_restart", type=int, default=0,
                   help="elastic: relaunch a failed local worker up to N "
                        "times before declaring the pod dead (reference "
                        "fleet/elastic/manager.py max_restart)")
    p.add_argument("--elastic_level", type=int, default=0,
                   help="0=off (fail fast), 1=restart failed workers")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--devices", default=None,
                   help="accepted for reference-API parity (TPU chips are "
                        "owned by the single process per host)")
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


# PCI ids of Google's TPU chips (v2/v3, v4, v5p, v5e, v6e, 7x) — the facts
# JAX itself reads before it starts a backend
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063",
                    "0x006f", "0x0076"}


def _local_tpu_chips() -> int:
    """TPU chips on this host's PCI bus.  Read from sysfs, not from JAX:
    a launcher that started a backend would hold the chips its workers
    need."""
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as f:
                n += f.read().strip() in _TPU_PCI_DEVICES
        except OSError:
            continue
    return n


def _check_one_worker_per_tpu_host(nproc: int):
    """A chip belongs to one process at a time and every worker sees every
    chip of its host, so on a TPU host a second local worker fails or hangs
    reaching for chips the first one holds: an error here, not a race
    there.  Workers pinned off the TPU (``JAX_PLATFORMS`` without ``tpu``)
    are exempt."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if nproc < 2 or (platforms and "tpu" not in platforms.split(",")):
        return
    chips = _local_tpu_chips()
    if chips:
        sys.exit(
            f"launch: --nproc_per_node={nproc} on a host with {chips} TPU "
            "chip(s): a chip belongs to one process, and each worker would "
            "reach for all of them.  Run one worker per host (it drives "
            "every local chip), or pin the workers off the TPU with "
            "JAX_PLATFORMS=cpu.")


def _rendezvous_node_rank(master: str, nnodes: int) -> int:
    """Join the job through the master's TCPStore and claim a node index
    (reference: launch/controllers/master.py — nodes register with the
    HTTP/etcd master and are assigned ranks; here the KV master is the
    native TCPStore, hosted by whichever node binds the port first)."""
    from paddle_tpu.core.native.tcp_store import TCPStore

    host, port = master.split(":")[0], int(master.split(":")[1])
    store = None
    try:  # try to host (first node on the master machine wins the bind)
        store = TCPStore(host=host, port=port + 2, is_master=True,
                         world_size=nnodes)
        if store._local is not None:
            raise RuntimeError("no native store")
    except Exception:
        store = TCPStore(host=host, port=port + 2, is_master=False,
                         world_size=nnodes)
    rank = store.add("launch/node_join", 1) - 1
    # sweep=False: a node joining late (or re-rendezvousing after an
    # elastic relaunch) must pass via the lingering done sentinel
    store.barrier("launch/all_nodes", nnodes, timeout=300.0, sweep=False)
    # keep the hosting store alive for the job's lifetime
    global _RDZV_STORE
    _RDZV_STORE = store
    return rank


_RDZV_STORE = None


def launch_main(argv=None):
    args = _parse()
    nproc = args.nproc_per_node
    _check_one_worker_per_tpu_host(nproc)
    world = args.nnodes * nproc
    if str(args.rank) == "auto":
        args.rank = _rendezvous_node_rank(args.master, args.nnodes)
    else:
        args.rank = int(args.rank)
    log_files = []
    log_dir = args.log_dir
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)

    def spawn(local_rank):
        rank = args.rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_WORLD_SIZE": str(world),
            "PADDLE_MASTER": args.master,
            "MASTER_ENDPOINT": args.master,
        })
        cmd = [sys.executable, "-u", args.script, *args.script_args]
        if log_dir:
            lf = open(os.path.join(log_dir, f"workerlog.{rank}"), "ab")
            log_files.append(lf)
            return subprocess.Popen(cmd, env=env, stdout=lf, stderr=lf)
        return subprocess.Popen(cmd, env=env)

    procs = {lr: spawn(lr) for lr in range(nproc)}
    restarts = {lr: 0 for lr in range(nproc)}

    exit_code = 0
    try:
        while procs:
            for lr, pr in list(procs.items()):
                rc = pr.poll()
                if rc is None:
                    continue
                if rc == 0:
                    procs.pop(lr)
                    continue
                # worker failed: elastic level 1 relaunches it in place
                # (reference elastic manager restart path) up to
                # --max_restart times; otherwise fail the pod fast
                if args.elastic_level >= 1 and restarts[lr] < args.max_restart:
                    restarts[lr] += 1
                    sys.stderr.write(
                        f"launch: worker {lr} rc={rc}; elastic restart "
                        f"{restarts[lr]}/{args.max_restart}\n")
                    procs[lr] = spawn(lr)
                    continue
                exit_code = rc
                # a failed rank kills the pod (reference container watch)
                for other in procs.values():
                    if other.poll() is None:
                        other.send_signal(signal.SIGTERM)
                for other in procs.values():
                    try:
                        other.wait(timeout=30)
                    except Exception:
                        pass
                procs = {}
                break
            time.sleep(0.2)
    finally:
        for lf in log_files:
            lf.close()
        if exit_code != 0 and log_dir:
            # tail the failing logs (reference tail-on-failure)
            for rank in range(world):
                path = os.path.join(log_dir, f"workerlog.{rank}")
                if os.path.exists(path):
                    with open(path, "rb") as f:
                        tail = f.read()[-2000:]
                    sys.stderr.write(f"----- {path} -----\n")
                    sys.stderr.buffer.write(tail)
                    sys.stderr.write("\n")
    return exit_code


if __name__ == "__main__":
    sys.exit(launch_main())
