"""Convergence parity: compressed-DP vs dense-DP at equal steps.

Reference parity: the reference's de-facto verification strategy is
convergence-as-test (SURVEY.md §4 item 1 — GaussianK@low density reaches
~dense accuracy). This script produces that evidence offline: it trains the
same model with the same seeds under several exchange/compressor arms on the
8-way virtual mesh and records final loss/top-1 per arm plus per-step curves.

Arms: dense psum | gaussian@density (allgather) | topk@density (allgather) |
gaussian@density (gTop-k butterfly, SURVEY.md §2.3) — i.e. both the C2 and
C3 communication paths of the reference. An arm spec may carry a
``:wire=off`` suffix (e.g. ``gaussian_fused,gaussian_fused:wire=off``) to
pin the legacy i32+f32 exchange — the packed-wire convergence control of
ISSUE 5 (parallel/wire.py): same plan, same selection, only the wire
differs.

Artifacts (analysis/artifacts/):
  convergence_parity.json — summary table (+ bytes/step per arm)
  convergence_parity_curves.jsonl — per-arm loss curves
  convergence_parity.png — plot (when matplotlib is available)

Run: python analysis/convergence_parity.py [--steps 300] [--density 0.01]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gaussiank_sgd_tpu import compile_cache, virtual_cpu  # noqa: E402

ARTIFACTS = os.path.join(REPO, "analysis", "artifacts")


def run_arm(name, steps, density, outdir, **overrides):
    """One training arm. Experiment-defining hyperparameters (dnn, dataset,
    batch_size, lr, ...) come from the caller via ``overrides`` — main() is
    the single source of their defaults (the argparse surface)."""
    import json as _json

    from gaussiank_sgd_tpu.training.config import TrainConfig
    from gaussiank_sgd_tpu.training.trainer import Trainer

    cfg = dict(
        momentum=0.9, epochs=1, max_steps=steps,
        compressor="gaussian", density=density,
        warmup_epochs=0.0, compute_dtype="float32", output_dir=outdir,
        log_every=10, eval_every_epochs=0, save_every_epochs=0, seed=0,
        run_id=name,
    )
    cfg.update(overrides)
    t = Trainer(TrainConfig(**cfg))
    t.train(steps)
    res = t.test()
    recs = [_json.loads(l) for l in open(
        os.path.join(t.run_dir, "metrics.jsonl"))]
    tr = [r for r in recs if r.get("event") == "train"]
    t.close()
    return {
        "arm": name,
        "compressor": cfg["compressor"],      # provenance: what actually ran
        "exchange": cfg.get("exchange", "allgather"),
        # wire format the sparse bytes traveled in (BASELINE.md protocol:
        # a bytes claim never goes out without its format name)
        "wire_format": next(
            (r["wire_format"] for r in reversed(tr)
             if r.get("wire_format") is not None), None),
        "final_loss": tr[-1]["loss"],
        "val_loss": res["val_loss"],
        "top1": res.get("top1"),
        "perplexity": res.get("perplexity"),
        "cer": res.get("cer"),
        # last-step exchange payload; the dense arm's value is its FULL
        # dense gradient (no compression)
        "bytes_per_step": tr[-1]["bytes_sent"],
        "curve": [(r["step"], r["loss"]) for r in tr],
    }


def _agg(vals):
    """mean ± sample spread over seeds; None-safe."""
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    import numpy as np
    return {"mean": round(float(np.mean(vals)), 4),
            "std": round(float(np.std(vals)), 4),
            "n": len(vals), "values": [round(float(v), 4) for v in vals]}


DEFAULT_ARMS = "none,gaussian,topk,gaussian@gtopk"


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--density", type=float, default=0.01)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--dnn", default="mnistnet")
    p.add_argument("--dataset", default="mnist")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.005)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--compress-warmup-steps", type=int, default=10)
    p.add_argument("--clip-norm", dest="clip_norm", type=float, default=None,
                   help="global grad-norm clip (the reference's LSTM "
                        "setting, SURVEY.md §3.2)")
    p.add_argument("--arms", default=DEFAULT_ARMS,
                   help="comma list of compressor[@exchange][:wire=off]; "
                        "'none' = the dense baseline arm, ':wire=off' pins "
                        "the legacy i32+f32 exchange format")
    p.add_argument("--bucket-size", dest="bucket_size", type=int,
                   default=None)
    p.add_argument("--bucket-policy", dest="bucket_policy",
                   choices=("greedy", "uniform"), default="greedy",
                   help="bucket plan passthrough — 'uniform' with "
                        "bucket_size <= 65536 makes arms wire-eligible "
                        "at any model scale")
    p.add_argument("--seeds", type=int, default=1,
                   help="run every arm with seeds 0..N-1 and report "
                        "mean +/- std per arm (error bars, VERDICT r2 "
                        "item 3)")
    p.add_argument("--label-noise", dest="label_noise", type=float,
                   default=0.0,
                   help="symmetric label-flip fraction p: top-1 ceiling "
                        "becomes 1-p, so the dense arm cannot saturate and "
                        "a compression-induced gap is measurable")
    p.add_argument("--model-kwargs", dest="model_kwargs", type=json.loads,
                   default={}, help="JSON model ctor overrides (toy sizes)")
    p.add_argument("--dataset-kwargs", dest="dataset_kwargs",
                   type=json.loads, default={},
                   help="JSON dataset overrides (e.g. bptt/vocab)")
    p.add_argument("--data-dir", dest="data_dir", default=None,
                   help="real dataset files (default: synthetic stand-in)")
    p.add_argument("--tag", default=None,
                   help="artifact suffix (default: the dnn when not "
                        "mnistnet)")
    p.add_argument("--outdir", default="/tmp/gksgd_parity")
    args = p.parse_args(argv)

    virtual_cpu.provision(args.devices)
    compile_cache.enable_compile_cache()
    os.makedirs(ARTIFACTS, exist_ok=True)

    dataset_kwargs = dict(args.dataset_kwargs)
    if args.label_noise > 0:
        # only the classification factories accept label_noise; fail at the
        # CLI with a clear message instead of a TypeError deep in dataset
        # construction (ADVICE r3)
        if args.dataset not in ("mnist", "cifar10", "cifar100"):
            p.error(f"--label-noise applies to the mnist/cifar10/cifar100 "
                    f"factories only, not {args.dataset!r}")
        dataset_kwargs["label_noise"] = args.label_noise
    common = dict(dnn=args.dnn, dataset=args.dataset,
                  batch_size=args.batch_size, lr=args.lr,
                  weight_decay=args.weight_decay, nworkers=args.devices,
                  data_dir=args.data_dir,
                  model_kwargs=args.model_kwargs,
                  dataset_kwargs=dataset_kwargs,
                  clip_norm=args.clip_norm,
                  bucket_size=args.bucket_size,
                  bucket_policy=args.bucket_policy,
                  compress_warmup_steps=args.compress_warmup_steps)
    from gaussiank_sgd_tpu.compressors import NAMES as COMP_NAMES
    arms = []
    for spec_str in args.arms.split(","):
        base, _, opt = spec_str.strip().partition(":")
        comp, _, exch = base.partition("@")
        if comp not in COMP_NAMES:
            p.error(f"bad arm spec {spec_str!r}: compressor must be one of "
                    f"{COMP_NAMES}")
        if exch and exch not in ("allgather", "gtopk"):
            p.error(f"bad arm spec {spec_str!r}: exchange must be "
                    f"allgather or gtopk")
        if opt and opt != "wire=off":
            p.error(f"bad arm spec {spec_str!r}: the only option is "
                    f":wire=off")
        name = comp if comp != "none" else "dense"
        ov = dict(compressor=comp)
        if exch:
            name += f"_{exch}"
            ov["exchange"] = exch
        if opt:
            name += "_wireoff"
            ov["wire"] = "off"
        arms.append((name, ov))
    results = []          # one aggregated record per arm
    for name, ov in arms:
        runs = []
        for s in range(args.seeds):
            print(f"=== arm {name} seed {s} ===", flush=True)
            dkw = dict(common["dataset_kwargs"], seed=100 + s)
            runs.append(run_arm(
                f"{name}_s{s}", args.steps, args.density, args.outdir,
                **{**common, "dataset_kwargs": dkw}, **ov, seed=s))
        r = dict(runs[0])                       # arm metadata + seed-0 curve
        r["arm"] = name
        r["seed_runs"] = [{k: run[k] for k in
                           ("final_loss", "val_loss", "top1", "perplexity",
                            "cer")}
                          for run in runs]
        for key in ("final_loss", "val_loss", "top1", "perplexity", "cer"):
            r[key + "_agg"] = _agg([run[key] for run in runs])
            r[key] = r[key + "_agg"]["mean"] if r[key + "_agg"] else None
        results.append(r)
        print(f"{name}: final_loss={r['final_loss']:.4f} "
              f"val_loss={r['val_loss']:.4f} top1={r['top1']} "
              f"bytes/step={r['bytes_per_step']}", flush=True)

    dense = next((r for r in results if r["compressor"] == "none"), None)
    summary = {
        "config": {"steps": args.steps, "density": args.density,
                   "nworkers": args.devices, "model": args.dnn,
                   "seeds": args.seeds, "label_noise": args.label_noise,
                   "dataset": args.dataset + (
                       f"(real: {args.data_dir})" if args.data_dir
                       else "(synthetic)"),
                   # built from vars(args) so every flag that shaped the
                   # run is recorded automatically
                   "reproduce": "python analysis/convergence_parity.py " +
                                " ".join(
                       f"--{k.replace('_', '-')} "
                       f"{json.dumps(v) if isinstance(v, dict) else v}"
                       for k, v in sorted(vars(args).items())
                       if v not in (None, "") and v != {})},
        "arms": [{k: r.get(k) for k in
                  ("arm", "compressor", "exchange", "wire_format",
                   "final_loss",
                   "val_loss", "top1", "perplexity", "cer",
                   "bytes_per_step", "final_loss_agg", "val_loss_agg",
                   "top1_agg", "perplexity_agg", "cer_agg")}
                 for r in results],
    }
    if dense is not None:   # a parity block only makes sense vs a dense arm
        def paired_gap(r, key, rel=False):
            """Seed-paired gap (dense_s - arm_s): level variation across
            seeds cancels, leaving the compression effect ± its spread."""
            gaps = []
            for da, ra in zip(dense["seed_runs"], r["seed_runs"]):
                if da[key] is None or ra[key] is None:
                    continue
                if rel and da[key] == 0:       # fully-saturated dense arm:
                    continue                   # a ratio is undefined, skip
                gaps.append((ra[key] / da[key]) if rel
                            else (da[key] - ra[key]))
            return _agg(gaps)

        summary["parity"] = {
            r["arm"]: {
                "top1_gap_vs_dense": paired_gap(r, "top1"),
                "val_loss_ratio_vs_dense": paired_gap(r, "val_loss",
                                                      rel=True),
                "perplexity_ratio_vs_dense": paired_gap(r, "perplexity",
                                                        rel=True),
                "cer_gap_vs_dense": paired_gap(r, "cer"),
            } for r in results if r is not dense
        }
    tag = (f"_{args.tag.lstrip('_')}" if args.tag else
           ("" if args.dnn == "mnistnet" else f"_{args.dnn}"))
    with open(os.path.join(ARTIFACTS,
                           f"convergence_parity{tag}.json"), "w") as f:
        json.dump(summary, f, indent=2)
    with open(os.path.join(ARTIFACTS,
                           f"convergence_parity{tag}_curves.jsonl"),
              "w") as f:
        for r in results:
            f.write(json.dumps({"arm": r["arm"], "curve": r["curve"]}) + "\n")
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, ax = plt.subplots(figsize=(7, 4.5))
        for r in results:
            xs, ys = zip(*r["curve"])
            ax.plot(xs, ys, label=r["arm"])
        ax.set_xlabel("step"); ax.set_ylabel("train loss")
        ax.set_title(f"{args.dnn}: compressed vs dense DP, "
                     f"density={args.density}, {args.devices}-way")
        ax.legend(); fig.tight_layout()
        fig.savefig(os.path.join(ARTIFACTS,
                                 f"convergence_parity{tag}.png"), dpi=120)
    except Exception as e:  # matplotlib optional on this machine
        print(f"(no plot: {e})")
    print(json.dumps(summary.get("parity", summary["arms"]), indent=2))
    return summary


if __name__ == "__main__":
    main()
