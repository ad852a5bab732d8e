"""dist.spawn (reference: `python/paddle/distributed/spawn.py:428`)."""
from __future__ import annotations

import multiprocessing
import os


def _wrap(func, rank, nprocs, master, args):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_MASTER"] = master
    endpoints = [f"127.0.0.1:{int(master.split(':')[1]) + i}" for i in range(nprocs)]
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
    os.environ["PADDLE_CURRENT_ENDPOINT"] = endpoints[rank]
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs < 1:
        nprocs = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
    if nprocs > 1:
        # same rule as the launch CLI: N > 1 local processes are the CPU
        # simulation; the children inherit the pin through the environment
        from .launch.main import _cpu_only_platform
        os.environ["JAX_PLATFORMS"] = _cpu_only_platform(os.environ, nprocs)
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    master = f"127.0.0.1:{port}"
    ctx = multiprocessing.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_wrap, args=(func, rank, nprocs, master, args),
                        daemon=daemon)
        p.start()
        procs.append(p)

    class Context:
        def __init__(self, processes):
            self.processes = processes

        def join(self):
            for p in self.processes:
                p.join()

    c = Context(procs)
    if join:
        c.join()
    return c
