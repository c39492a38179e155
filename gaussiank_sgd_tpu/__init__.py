"""gaussiank_sgd_tpu — a TPU-native framework for communication-compressed
synchronous data-parallel training.

Built from scratch in JAX/XLA (pjit + shard_map + Pallas) with the capability
surface of the reference ``sb17v/GaussianK-SGD`` (PyTorch + Horovod/NCCL/MPI).
See ``SURVEY.md`` at the repo root for the reference analysis this framework is
built against; the reference mount was empty at survey time, so reference
citations throughout this package are file-level (SURVEY.md section numbers)
rather than file:line.

Layer map (TPU-native; compare SURVEY.md §1.1):

    cli / launch scripts        -> gaussiank_sgd_tpu.train (argparse entry)
    trainer runtime             -> gaussiank_sgd_tpu.training.trainer
    distributed optimizer       -> gaussiank_sgd_tpu.parallel.trainstep
    compression                 -> gaussiank_sgd_tpu.compressors
    comms backend               -> XLA collectives over the ICI/DCN device mesh
                                   (gaussiank_sgd_tpu.parallel.{mesh,collectives})
    hot select kernel           -> gaussiank_sgd_tpu.ops.pallas_pack
"""

__version__ = "0.1.0"
