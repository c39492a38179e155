"""Distributed training entrypoint.

Reference parity: ``horovod_trainer.py`` (SURVEY.md §2 C6, §3.1) — the
argparse CLI, process/device initialization, trainer construction, and the
epoch loop. The launch model is TPU-native: instead of
``mpirun -np P python horovod_trainer.py``, run ONE process per host
(``python -m gaussiank_sgd_tpu.train ...``); `jax.distributed` + the slice
topology replace MPI rank discovery (SURVEY.md §2.1), and the dp width is
the device mesh, not a process count.

Examples (mirroring the reference's launch scripts, SURVEY.md §2 C12):
  # dense single-worker smoke (BASELINE config 1)
  python -m gaussiank_sgd_tpu.train --dnn resnet20 --dataset cifar10 \
      --nworkers 1 --compressor none --epochs 1 --max-steps 20

  # 8-way GaussianK at 0.1% density (BASELINE config 2 shape)
  python -m gaussiank_sgd_tpu.train --dnn vgg16 --dataset cifar10 \
      --nworkers 8 --compressor gaussian --density 0.001 \
      --compress-warmup-steps 100
"""

from __future__ import annotations

import argparse
import os
import sys

# Honor the virtual-CPU hook BEFORE any jax import side effect: with
# GKSGD_FORCE_VIRTUAL_CPU=<n> the CLI runs on an n-device virtual CPU mesh
# (multi-worker configs without hardware — SURVEY.md §4, scripts/run_all.sh).
_vcpu = os.environ.get("GKSGD_FORCE_VIRTUAL_CPU", "")
if _vcpu.strip():
    if not _vcpu.strip().isdigit() or int(_vcpu) <= 0:
        raise SystemExit(
            f"GKSGD_FORCE_VIRTUAL_CPU must be a positive device count, "
            f"got {_vcpu!r} (unset it to use the real backend)")
    from . import virtual_cpu

    virtual_cpu.provision(int(_vcpu))

from .compile_cache import enable_compile_cache
from .parallel.mesh import maybe_initialize_distributed
from .training.config import add_args, from_args
from .training.trainer import Trainer


def make_trainer(argv=None) -> Trainer:
    """Everything ``main`` does before the first step: parse ``argv``, place
    the compile cache, join the multi-host job if there is one, build the
    Trainer. Split out so a caller that needs the trainer object afterwards
    (chip_smoke.py) constructs it exactly as the CLI does."""
    if argv is None:
        argv = sys.argv[1:]     # pin what parse_args sees, so from_args's
                                # explicit-flag detection re-reads the SAME list
    p = argparse.ArgumentParser(
        description="TPU-native communication-compressed data-parallel "
                    "training (GaussianK-SGD capability surface)")
    add_args(p)
    args = p.parse_args(argv)
    enable_compile_cache()
    maybe_initialize_distributed()
    return Trainer(from_args(args, argv))


def main(argv=None):
    trainer = make_trainer(argv)
    try:
        result = trainer.fit()
        trainer.logger.info("done: %s", result)
        return result
    finally:
        trainer.close()


if __name__ == "__main__":
    main()
