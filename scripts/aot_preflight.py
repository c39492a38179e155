#!/usr/bin/env python3
"""Device-less AOT pre-flight: compile the step programs for a v5e without one.

libtpu compiles for a *topology description* on a machine that has no chip, so
the full dense and sparse step — Mosaic kernel included — can be compiled here
on the CPU host before chip budget is spent on a run that would have died in
the compiler. It prints, per program: compile seconds, ``tpu_custom_call``
count, the collectives left in the optimized HLO, XLA's FLOP count and its
memory analysis.

It proves COMPILATION ONLY. It runs nothing: not start-up, not placement, not
numerics, not time. Those are chip_smoke.py's, on the chip.

    python scripts/aot_preflight.py                       # vgg16, one chip
    python scripts/aot_preflight.py --chips 4
    python scripts/aot_preflight.py --model transformer --dataset wmt \\
        --batch-size 32
    python scripts/aot_preflight.py --model mellum2 --dataset ptb \\
        --batch-size 2 --model-kwargs '{"num_layers": 4, "expert_shares": 8,
        "vocab_size": 12288, "seq_len": 8192, "kernels": true}'
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

# the metadata-server query has nothing to answer it on a sealed machine;
# and no TPU client is created here, so libtpu's one-process lockfile (held
# by whatever else has libtpu loaded) guards nothing
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh

from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.models import get_model
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.training.losses import make_loss_fn

_COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _batch_shapes(spec, batch_size: int):
    """The (x, y) shapes and dtypes of one batch of the model task."""
    def ints(*shape):
        return jax.ShapeDtypeStruct((batch_size,) + shape, jnp.int32)
    x_float = jax.ShapeDtypeStruct((batch_size,) + spec.input_shape,
                                   jnp.float32)
    if spec.task == "classify":
        return x_float, ints()
    if spec.task in ("lm", "seq2seq"):
        return ints(spec.input_shape[0]), ints(spec.input_shape[0])
    if spec.task == "ctc":
        return x_float, ints(16)
    raise ValueError(spec.task)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="aot_preflight.py")
    ap.add_argument("--model", default="vgg16")
    ap.add_argument("--dataset", default="cifar10")
    ap.add_argument("--batch-size", type=int, default=128,
                    help="per worker")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--compressor", default="auto")
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--model-kwargs", type=json.loads, default={},
                    help="JSON, as TrainConfig.model_kwargs")
    args = ap.parse_args(argv)

    topo = topologies.get_topology_desc(args.topology, "tpu")
    mesh = Mesh(np.array(topo.devices[:args.chips]), ("dp",))
    print(f"target: {args.chips} x {topo.devices[0].device_kind!r} "
          f"(device-less); this process's backend: {jax.default_backend()}")

    spec = get_model(args.model, args.dataset, dtype=jnp.bfloat16,
                     **args.model_kwargs)
    recurrent = args.model == "lstm"
    batch = _batch_shapes(spec, args.batch_size * args.chips)
    two = _batch_shapes(spec, 2)
    init_in = two if spec.task == "seq2seq" else two[:1]
    variables = jax.eval_shape(
        lambda *a: spec.module.init({"params": jax.random.PRNGKey(0)}, *a,
                                    train=False), *init_in)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}
    plan = plan_for_params(params, args.density)
    ts = build_dp_train_step(
        make_loss_fn(spec, recurrent=recurrent), None,
        get_compressor(args.compressor, density=args.density), plan, mesh,
        recurrent=recurrent, flat_opt=FlatSGDM(lr=0.1, momentum=0.9))
    carry = (jax.eval_shape(lambda: spec.module.initial_carry(
        args.batch_size * args.chips)) if recurrent else ())
    state = jax.eval_shape(
        lambda p, m, c: ts.init_state(p, jax.random.PRNGKey(2),
                                      model_state=m, carry=c),
        params, mstate, carry)
    print(f"{args.model}: {plan.total_numel} parameters, "
          f"{len(plan.buckets)} bucket(s), k={plan.total_k}, "
          f"ef_numel={ts.ef_numel}, kernel={ts.kernel_mode}, "
          f"wire={ts.wire_format}, overlap={ts.overlap}")

    for name, fn in (("dense", ts.dense_step), ("sparse", ts.sparse_step)):
        lowered = fn.lower(state, batch)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s, "
              f"{lowered.as_text().count('tpu_custom_call')} "
              f"tpu_custom_call, "
              f"{compiled.cost_analysis().get('flops', 0):.4g} flop/step")
        for line in compiled.as_text().splitlines():
            if _COLLECTIVE.search(line):
                print("   ", line.strip()[:200])
        print("   ", compiled.memory_analysis())


if __name__ == "__main__":
    main()
