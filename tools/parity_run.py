"""One-command real-data parity runner (PARITY.md "Reference targets
awaiting a data mount").

Usage::

    FEDML_DATA_DIR=/mnt/fedml_data python tools/parity_run.py [--gate NAME]
    python tools/parity_run.py --dry-run        # synthetic smoke, no mount

For every gate whose dataset is present under the mount, runs the
benchmark-shaped config end-to-end (the same configs as
tests/test_parity.py::TestRealDataGates, thresholds from the reference
benchmark tables: doc/en/simulation/benchmark/BENCHMARK_MPI.md:9,99-108)
and APPENDS a result row to PARITY.md, so the measured-parity record
accretes run over run.  With no mount (or --dry-run) each gate executes a
tiny synthetic-shape version to prove the runner itself end-to-end, and
nothing is appended.
"""

from __future__ import annotations

import argparse
import datetime
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# gate -> (dataset, config builder kwargs, threshold, reference citation)
GATES = {
    "mnist_lr_200_rounds": dict(
        dataset="mnist", model="lr", clients=(1000, 10), rounds=200,
        batch=10, lr=0.03, threshold=0.75,
        ref="BENCHMARK_MPI.md:9 (target >75)",
    ),
    "cifar10_resnet56_trajectory": dict(
        dataset="cifar10", model="resnet56", clients=(10, 10), rounds=50,
        batch=64, lr=0.1, threshold=0.35,
        ref="BENCHMARK_MPI.md:101 (50-round trajectory toward 93.19 IID)",
    ),
    "femnist_cnn": dict(
        dataset="femnist", model="cnn", clients=(200, 10), rounds=100,
        batch=20, lr=0.03, threshold=0.60,
        ref="BENCHMARK_simulation.md (fed EMNIST + CNN, 84.9 full-scale)",
    ),
}


def _cfg(gate: str, g: dict, data_dir: str, synthetic: bool) -> dict:
    rounds = 2 if synthetic else g["rounds"]
    clients = (8, 4) if synthetic else g["clients"]
    batch = min(g["batch"], 16) if synthetic else g["batch"]
    return {
        "common_args": {"training_type": "simulation", "random_seed": 0,
                        "run_id": f"parity-run-{gate}"},
        "data_args": {"dataset": g["dataset"],
                      "data_cache_dir": "" if synthetic else data_dir,
                      "partition_method": "hetero", "partition_alpha": 0.5,
                      "synthetic_train_size": 512},
        "model_args": {"model": g["model"]},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": clients[0],
                       "client_num_per_round": clients[1],
                       "comm_round": rounds, "epochs": 1,
                       "batch_size": batch, "client_optimizer": "sgd",
                       "learning_rate": g["lr"]},
        "validation_args": {"frequency_of_the_test": max(rounds // 2, 1)},
        "comm_args": {"backend": "XLA"},
    }


def _run(cfg: dict) -> dict:
    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.simulator import create_simulator

    args = fedml_tpu.init(Arguments.from_dict(cfg).validate(),
                          should_init_logs=False)
    device = fedml_tpu.device.get_device(args)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    return create_simulator(args, device, dataset, model).run()


def _dataset_mounted(name: str, data_dir: str) -> bool:
    from fedml_tpu.data.loaders import try_load_real

    try:
        return try_load_real(name, data_dir) is not None
    except Exception:
        return False


def _append_parity(rows: list) -> None:
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d %H:%MZ")
    path = os.path.join(REPO, "PARITY.md")
    with open(path, "a") as f:
        f.write(f"\n## Real-data parity run — {stamp}\n\n")
        f.write("| Gate | Threshold | Measured | Status | Reference |\n")
        f.write("|---|---|---|---|---|\n")
        for gate, thr, acc, ok, ref in rows:
            f.write(f"| {gate} | >={thr} | {acc:.4f} | "
                    f"{'pass' if ok else 'FAIL'} | {ref} |\n")
    print(f"appended {len(rows)} result row(s) to PARITY.md")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gate", action="append",
                    help="run only this gate (repeatable); default: all")
    ap.add_argument("--dry-run", action="store_true",
                    help="synthetic smoke of every gate; nothing appended")
    args = ap.parse_args()

    data_dir = os.environ.get("FEDML_DATA_DIR", os.path.join(REPO, "fedml_data"))
    if args.gate:
        unknown = [g for g in args.gate if g not in GATES]
        if unknown:
            # every requested name must resolve: a silently-dropped typo
            # would leave a gate unmeasured while PARITY.md looks complete
            print(f"unknown gate(s) {unknown}; known: {sorted(GATES)}")
            return 2
    gates = {k: v for k, v in GATES.items()
             if not args.gate or k in args.gate}

    if args.dry_run:
        # dry-run needs no chip: pin the CPU before the first backend use
        # (same policy as tests/conftest.py)
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        from fedml_tpu.utils.platform import force_cpu_backend

        force_cpu_backend()

    rows, failures = [], 0
    for gate, g in gates.items():
        synthetic = args.dry_run or not _dataset_mounted(g["dataset"], data_dir)
        mode = "synthetic dry-run" if synthetic else f"REAL data ({data_dir})"
        print(f"== {gate}: {mode} ==")
        metrics = _run(_cfg(gate, g, data_dir, synthetic))
        acc = float(metrics.get("test_acc", 0.0))
        if synthetic:
            print(f"   dry-run completed (acc {acc:.4f}; threshold not applied)")
            continue
        ok = acc >= g["threshold"]
        failures += 0 if ok else 1
        print(f"   acc {acc:.4f} vs threshold {g['threshold']}: "
              f"{'pass' if ok else 'FAIL'}")
        rows.append((gate, g["threshold"], acc, ok, g["ref"]))
    if rows:
        _append_parity(rows)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
