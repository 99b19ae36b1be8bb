"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On a CPU it trains reduced/small configs end-to-end (the examples use
it); on a TPU pod slice the same launcher drives the production mesh — the
mesh/rules wiring, checkpointing, heartbeat, and straggler policy are
identical in both modes (hardware-agnostic launch, the HALO property applied
to the launcher).
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..data.pipeline import SyntheticLM
from ..distributed.sharding import mesh_context
from ..models import build_model
from ..train.checkpoint import CheckpointManager
from ..train.fault_tolerance import HeartbeatJournal, StragglerPolicy
from ..train.trainer import TrainHyper, Trainer
from .cache import use_compile_cache
from .mesh import make_debug_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--comm", type=int, default=0, metavar="N",
                    help="train data-parallel over an N-member C²MPI device "
                         "group (cycling the available substrates); "
                         "microbatches is raised to a multiple of N")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--heartbeat", default=None)
    ap.add_argument("--mesh", choices=["none", "debug", "single", "multi"],
                    default="none")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)

    comm = None
    microbatches = args.microbatches
    if args.comm:
        from ..core.c2mpi import MPIX_Initialize, halo_session
        from ..core.collective import comm_split
        MPIX_Initialize()
        session = halo_session()
        subs = comm_split(session).platforms   # available substrates
        comm = comm_split(
            session, [subs[i % len(subs)] for i in range(args.comm)])
        microbatches = -(-microbatches // args.comm) * args.comm
    hp = TrainHyper(base_lr=args.lr, warmup_steps=max(1, args.steps // 10),
                    total_steps=args.steps, microbatches=microbatches,
                    compress_grads=args.compress_grads)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    hb = HeartbeatJournal(args.heartbeat) if args.heartbeat else None
    trainer = Trainer(model=model, hp=hp, ckpt=ckpt, heartbeat=hb,
                      straggler=StragglerPolicy(), comm=comm, arch=args.arch,
                      arch_reduced=args.reduced)

    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    pipe = SyntheticLM(cfg, seq_len=args.seq_len, global_batch=args.batch,
                       seed=args.seed)

    def data_fn(step):
        return {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}

    with mesh_context(mesh):
        state, start = trainer.restore_or_init(jax.random.PRNGKey(args.seed))
        state, history = trainer.run(state, data_fn,
                                     steps=args.steps - start,
                                     start_step=start)
    print("final loss:", history[-1][1] if history else None)
    return history


if __name__ == "__main__":
    main()
