"""Per-primitive cost of the sparse-step overhead at contract scale.

The program-level ablation (sparse_ablation.py) prices pipeline PREFIXES;
this prices the individual primitives so the optimization target is
unambiguous (VERDICT r4 item 5: "profile what's left"). Primitives, all at
n = 57M, k = 57k (density 0.001, config-5 scale), f32:

  ef_accumulate      acc = acc + grad                 (2 reads + 1 write)
  kernel_pass        scale acc + fused candidate extraction (vs scale_only)
  scale_only         acc = acc * c  — baseline pass the kernel body adds
  cand_topk_exact    lax.top_k over the ~n/SEG candidate buffer
  cand_topk_approx   lax.approx_max_k over the same buffer (r=0.95)
  residual_scatter   acc.at[idx].set(c)  (k random updates into n)
  decompress_scatter zeros(n).at[idx].add(val) (+ sorted/unique variant)
  sort_k_pairs       lax.sort of the k (idx, val) pairs
  sgd_update         optax sgd+momentum over n

Measurement discipline: single-dispatch timings are dominated by host
latency (benchlib.py module docstring), so every primitive runs
``n_steps`` iterations inside ONE jitted ``fori_loop`` whose carry is the
full array the primitive touches — a loop-carried dependence XLA cannot
hoist or DCE — and fences through a scalar ``float()``. Reported ms =
(loop time)/n_steps, median over rounds.

Artifact: analysis/artifacts/overhead_microbench.json (57M default);
``--config config2|config4`` re-prices every primitive at that BASELINE
config's own gradient size (the r6 gap: the binding vgg16 config was
never profiled at its own ~15M scale) and writes
overhead_microbench_<config>.json; ``--tag`` overrides the suffix.
Run (TPU): python analysis/overhead_microbench.py [--config config2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ARTIFACTS = os.path.join(REPO, "analysis", "artifacts")


# bench.py config key -> (model, dataset); n is resolved to the model's
# actual param count at runtime (roofline.param_count), so the microbench
# scale can never drift from what the bench measures
CONFIG_MODELS = {
    "config2": ("vgg16", "cifar10"),
    "config4": ("lstm", "ptb"),
    "config5": ("transformer", "wmt"),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=57_000_000)
    p.add_argument("--config", choices=sorted(CONFIG_MODELS),
                   help="price the primitives at this BASELINE config's "
                        "own param count instead of --n")
    p.add_argument("--tag", default=None,
                   help="artifact suffix: overhead_microbench_<tag>.json "
                        "(defaults to --config when given)")
    p.add_argument("--density", type=float, default=0.001)
    p.add_argument("--n-steps", type=int, default=20)
    p.add_argument("--rounds", type=int, default=5)
    args = p.parse_args()

    config_model = None
    if args.config:
        from roofline import param_count
        config_model = CONFIG_MODELS[args.config]
        args.n = param_count(*config_model)
        if args.tag is None:
            args.tag = args.config

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from gaussiank_sgd_tpu.ops.pallas_pack import (
        _chunk_geometry, ef_padded_chunk, fused_ef_select_candidates_chunked,
        fused_select_candidates)

    n, k = args.n, int(args.n * args.density)
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    acc = jax.random.normal(k1, (n,), jnp.float32)
    grad = jax.random.normal(k2, (n,), jnp.float32)
    idx = jnp.sort(jax.random.permutation(k3, n)[:k].astype(jnp.int32))
    val = acc[idx]
    _, _, _, nc = _chunk_geometry(n, args.density)
    cand = jax.random.normal(k2, (nc,), jnp.float32)

    opt = optax.sgd(0.1, momentum=0.9)

    def timeit(body, init, rounds=args.rounds, n_steps=args.n_steps):
        """body: carry -> carry with a loop-carried full-array dependence."""
        @jax.jit
        def run(carry):
            return lax.fori_loop(0, n_steps, lambda i, c: body(c), carry)

        out = run(init)
        _ = float(jax.tree_util.tree_leaves(out)[0].ravel()[0])  # warm+fence
        ts = []
        for _r in range(rounds):
            t0 = time.perf_counter()
            out = run(init)
            _ = float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
            ts.append(1e3 * (time.perf_counter() - t0) / n_steps)
        return round(statistics.median(ts), 3)

    ms = {}
    ms["ef_accumulate"] = timeit(lambda a: a + grad, acc)
    ms["scale_only"] = timeit(lambda a: a * jnp.float32(1.0000001), acc)

    def kernel_body(a):
        a = a * jnp.float32(1.0000001)
        vals, idxs, count = fused_select_candidates(a, jnp.float32(3.0),
                                                    args.density)
        # fold the candidate result back so it cannot be dropped
        return a + (count.astype(jnp.float32) * jnp.float32(0.0))
    ms["kernel_pass_incl_scale"] = timeit(kernel_body, acc)
    ms["kernel_pass"] = round(ms["kernel_pass_incl_scale"]
                              - ms["scale_only"], 3)

    # the single-pass fused EF+select form (ops/pallas_pack.py): reads
    # residual + grad, writes the accumulator, emits candidates in ONE
    # kernel. Compare against ef_accumulate + kernel_pass — the two
    # n-sized passes it replaces.
    cp = ef_padded_chunk(n, k, density=args.density)
    if cp is not None:
        g_pad = jnp.pad(grad, (0, cp - n)).reshape(1, cp)
        thr = jnp.full((1,), 3.0, jnp.float32)

        def fused_ef_body(res):
            a2, _vals, _idxs, counts = fused_ef_select_candidates_chunked(
                res, g_pad, jnp.float32(1e-6), thr, args.density)
            # fold count back so the candidate emission cannot be DCE'd;
            # the tiny grad scale keeps the loop-carried residual finite
            return a2 + (counts[0].astype(jnp.float32) * jnp.float32(0.0))
        ms["fused_ef_select_pass"] = timeit(
            fused_ef_body, jnp.pad(acc, (0, cp - n)).reshape(1, cp))

    def topk_body(c):
        kv, ki = lax.top_k(jnp.abs(c), k)
        return c.at[ki[0]].add(kv[0] * jnp.float32(1e-12))
    ms["cand_topk_exact"] = timeit(topk_body, cand)

    def topk_approx_body(c):
        kv, ki = lax.approx_max_k(jnp.abs(c), k, recall_target=0.95)
        return c.at[ki[0]].add(kv[0] * jnp.float32(1e-12))
    ms["cand_topk_approx"] = timeit(topk_approx_body, cand)

    ms["residual_scatter"] = timeit(
        lambda a: a.at[idx].set(a[0] * jnp.float32(1e-9)), acc)
    ms["residual_scatter_sorted"] = timeit(
        lambda a: a.at[idx].set(a[0] * jnp.float32(1e-9),
                                indices_are_sorted=True,
                                unique_indices=True), acc)

    def dec_body(b):
        return jnp.zeros((n,), jnp.float32).at[idx].add(val + b[0])
    ms["decompress_scatter"] = timeit(dec_body, jnp.zeros((n,), jnp.float32))

    def dec_sorted_body(b):
        return jnp.zeros((n,), jnp.float32).at[idx].add(
            val + b[0], indices_are_sorted=True, unique_indices=True)
    ms["decompress_scatter_sorted"] = timeit(
        dec_sorted_body, jnp.zeros((n,), jnp.float32))

    ms["sort_k_pairs"] = timeit(
        lambda iv: tuple(lax.sort(list(iv), num_keys=1)),
        (idx, val))

    def sgd_body(carry):
        params, ostate = carry
        up, ostate = opt.update({"w": grad}, ostate, params)
        return optax.apply_updates(params, up), ostate
    params0 = {"w": acc}
    ms["sgd_update"] = timeit(sgd_body, (params0, opt.init(params0)))

    res = {
        "shapes": {"n": n, "k": k, "candidates": nc},
        "config": ({"key": args.config, "model": config_model[0],
                    "dataset": config_model[1]} if config_model else None),
        "method": f"fori_loop x{args.n_steps} per dispatch, loop-carried "
                  f"arrays, scalar fence; median of {args.rounds} rounds",
        "ms": ms,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0].device_kind),
    }
    os.makedirs(ARTIFACTS, exist_ok=True)
    name = ("overhead_microbench.json" if not args.tag
            else f"overhead_microbench_{args.tag}.json")
    with open(os.path.join(ARTIFACTS, name), "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
