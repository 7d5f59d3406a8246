"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --shape train_4k --steps 100 --mesh single          # on a pod
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --smoke --steps 50 --mesh local                     # on this host

Builds the mesh, sharded train state, host-sharded data pipeline, and runs
under the fault-tolerant TrainDriver (auto-restart from checkpoints,
straggler watchdog). The same script is what a multi-host deployment runs
per process — jax.distributed.initialize() is called when the usual TPU
environment variables are present. The launcher exits non-zero when a step
reports a non-finite loss.
"""

from __future__ import annotations

import argparse
import math
import os
import sys

import jax
import numpy as np

from repro.configs.base import SHAPES, TrainConfig
from repro.configs.registry import get_config, get_smoke
from repro.data.pipeline import SyntheticLM, host_sharded_batch
from repro.dist.sharding import param_shardings, opt_shardings
from repro.ft.driver import TrainDriver
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.specs import build_model, state_specs
from repro.nn.module import init_params
from repro.train.loop import init_train_state, make_train_step


def build_trainer(cfg, tcfg: TrainConfig, mesh, *, seq: int, batch: int):
    """(driver, initial state) for ``cfg`` on ``mesh``: state placed as
    ``state_specs`` shards it, the jitted train step, and synthetic batches
    of ``batch`` sequences of ``seq`` tokens sharded over the data axis.
    Enter ``mesh`` around ``driver.run``."""
    model = build_model(cfg)
    from repro.dist.sharding import set_ambient_mesh
    set_ambient_mesh(mesh)
    _, shardings = state_specs(cfg, tcfg, mesh)
    with mesh:
        params = init_params(model.specs(), tcfg.seed)
        state = jax.device_put(init_train_state(params, tcfg), shardings)
    step_fn = jax.jit(make_train_step(model, cfg, tcfg, mesh=mesh),
                      in_shardings=(shardings, None),
                      # pinned, or XLA may hand back the state laid out
                      # otherwise and the next step's in_shardings refuse it
                      out_shardings=(shardings, None),
                      donate_argnums=(0,))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, batch=batch,
                       seed=tcfg.seed)

    def data_fn(step: int):
        return host_sharded_batch(mesh, data.batch_np(step))

    driver = TrainDriver(step_fn, tcfg, data_fn,
                         state_shardings=shardings, mesh=mesh)
    return driver, state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small synthetic shapes (CPU)")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "single", "multi"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    if "JAX_COORDINATOR" in os.environ:          # multi-host pod entry
        jax.distributed.initialize()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    shape = SHAPES[args.shape]
    seq = args.seq or (64 if args.smoke else shape.seq_len)
    batch = args.batch or (8 if args.smoke else shape.global_batch)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       microbatch=args.microbatch,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir,
                       z_loss=0.0 if args.smoke else 1e-4)

    enable_compile_cache()
    mesh = (make_local_mesh() if args.mesh == "local"
            else make_production_mesh(multi_pod=args.mesh == "multi"))
    driver, state = build_trainer(cfg, tcfg, mesh, seq=seq, batch=batch)
    with mesh:
        state = driver.run(state, n_steps=args.steps)

    for m in driver.metrics_log[-5:]:
        print(f"step {m['step']:5d} loss {m['loss']:.4f} ({m['dt']*1e3:.0f} ms)")
    print(f"restarts={driver.restarts} straggler_events={len(driver.watchdog.events)}")
    bad = [m["step"] for m in driver.metrics_log
           if not math.isfinite(m["loss"])]
    if bad:
        sys.exit(f"non-finite loss at steps {bad}")


if __name__ == "__main__":
    main()
