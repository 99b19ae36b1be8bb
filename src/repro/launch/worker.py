"""Remote worker entrypoint: ``python -m repro.launch.worker --connect
HOST:PORT [--name w0] [--platforms xla,jnp] [--devices N]``.

Spawned by :func:`repro.distributed.remote.spawn_worker`, which puts
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and
``JAX_PLATFORMS=cpu`` in this process's environment *before* it starts —
the flag only takes effect ahead of jax initialization, which is why
workers are fresh processes rather than forks.  The heavy imports happen
inside :func:`main` so ``--help`` and argument errors stay instant.

The worker dials back to the host, sends a hello frame, and serves
``exec``/``ping``/``chaos``/``release``/``shutdown`` frames until the
transport closes (DESIGN.md §13).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="host-side listener to dial back to")
    ap.add_argument("--name", default="w0")
    ap.add_argument("--platforms", default="xla,jnp",
                    help="comma-separated substrates this worker serves")
    ap.add_argument("--devices", type=int, default=None,
                    help="informational; the device count is fixed by "
                         "XLA_FLAGS at process start")
    ap.add_argument("--log-level", default=None)
    args = ap.parse_args(argv)
    if args.log_level is None:
        from repro.core.config import halo_config
        args.log_level = halo_config().worker_log
    logging.basicConfig(
        level=args.log_level.upper(),
        format=f"[{args.name}] %(levelname)s %(name)s: %(message)s")

    from ..distributed.remote import connect_and_serve
    platforms = [p.strip() for p in args.platforms.split(",") if p.strip()]
    connect_and_serve(args.connect, name=args.name, platforms=platforms)
    return 0


if __name__ == "__main__":
    sys.exit(main())
