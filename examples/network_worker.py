"""Standalone pipeline stage worker process.

Reference equivalent: ``examples/network_worker.cpp:14-195`` — the worker
half of the reference's headline deployment. Run one per stage host/process;
a :class:`DistributedPipelineCoordinator` (see ``distributed_trainer.py``)
connects, ships the stage config + weights, and drives training.

Usage:
  python examples/network_worker.py --port 9601
  # or env-configured (docker-compose style):
  WORKER_PORT=9601 python examples/network_worker.py

Flags mirror the reference CLI (network_worker.cpp getopt loop): --port,
--compress (zstd activation compression on the wire). The backend is JAX's
to choose: export JAX_PLATFORMS=cpu for a CPU worker. A TPU chip belongs to
one process, so at most one TPU worker runs per chip.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    ap = argparse.ArgumentParser(description="DCNN-TPU pipeline stage worker")
    ap.add_argument("--port", type=int,
                    default=int(os.environ.get("WORKER_PORT", "9601")))
    ap.add_argument("--compress", action="store_true",
                    default=os.environ.get("WORKER_COMPRESS", "") == "1")
    args = ap.parse_args()

    from dcnn_tpu.parallel.worker import run_worker

    print(f"[worker] listening on :{args.port} "
          f"(compress={'on' if args.compress else 'off'})", flush=True)
    run_worker(args.port, compress=args.compress)
    print("[worker] shutdown", flush=True)


if __name__ == "__main__":
    main()
