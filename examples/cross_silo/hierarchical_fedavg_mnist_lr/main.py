"""Hierarchical cross-silo (Octopus + the Cheetah intra-silo plane): every
client silo runs ``n_proc_in_silo`` processes — proc 0 owns the WAN
connection, slave procs train stride-shards of the silo's data over the
host ProcessGroup plane and join the weighted allreduce.  This main.py is a
self-contained torchrun stand-in: it spawns the silo's slave processes and
places each by env (FEDML_PROC_RANK_IN_SILO / MASTER_PORT — the same env
surface a real torchrun-style launcher would set).  A real launcher gives
each silo process its own accelerator host; this stand-in runs them all on
ONE host, so every process — the parent included — is placed on the host
CPU before it touches a jax backend (a chip belongs to one process).

    python main.py --cf fedml_config.yaml --role server --rank 0
    python main.py --cf fedml_config.yaml --role client --rank 1
    python main.py --cf fedml_config.yaml --role client --rank 2
"""
import multiprocessing as mp
import os
import sys

import yaml

import fedml_tpu
from fedml_tpu.utils.platform import force_cpu_backend


def _silo_proc(argv, proc_rank, n_proc, pg_port):
    force_cpu_backend()
    sys.argv = list(argv)
    os.environ["FEDML_PROC_RANK_IN_SILO"] = str(proc_rank)
    os.environ["FEDML_N_PROC_IN_SILO"] = str(n_proc)
    os.environ["MASTER_PORT"] = str(pg_port)
    fedml_tpu.run_cross_silo_client()


if __name__ == "__main__":
    role = "client"
    if "--role" in sys.argv:
        role = sys.argv[sys.argv.index("--role") + 1]
    if role == "server":
        fedml_tpu.run_cross_silo_server()
    else:
        cf = sys.argv[sys.argv.index("--cf") + 1] if "--cf" in sys.argv else "fedml_config.yaml"
        with open(cf) as f:
            cfg = yaml.safe_load(f)
        n_proc = int(cfg.get("train_args", {}).get("n_proc_in_silo", 1))
        rank = int(sys.argv[sys.argv.index("--rank") + 1]) if "--rank" in sys.argv else 1
        # one pg rendezvous port per silo
        pg_port = int(cfg.get("comm_args", {}).get("pg_base_port", 29420)) + rank
        ctx = mp.get_context("spawn")
        slaves = [
            ctx.Process(target=_silo_proc, args=(sys.argv, k, n_proc, pg_port), daemon=True)
            for k in range(1, n_proc)
        ]
        for p in slaves:
            p.start()
        _silo_proc(sys.argv, 0, n_proc, pg_port)
        for p in slaves:
            p.join()
