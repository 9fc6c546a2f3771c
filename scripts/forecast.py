#!/usr/bin/env python
"""Forecaster training + evaluation CLI (ROADMAP item 2 tooling).

Builds the (history-window -> next-window rate) dataset from the seeded
scenario families, trains the mLSTM forecaster on the jax_pallas train
substrate, scores it against the numpy baselines (EWMA, AR(1)) on the
held-out validation seeds, round-trips the result through the shared
`CheckpointManager`, and writes a JSON report::

    python scripts/forecast.py                        # full eval
    python scripts/forecast.py --smoke                # the CI gate
    python scripts/forecast.py --ckpt runs/forecast   # also keep params

All metrics are log1p-space MSE (the training objective): rates are
nonnegative and heavy-tailed across families, and log space stops
flash-crowd peaks from drowning the quiet regimes.

Requires JAX; `scripts/ci.sh` gates the call on ``import jax`` so
JAX-less environments skip it cleanly rather than half-running.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

DEFAULT_FAMILIES = ("diurnal", "flash-crowd", "heavy-tail", "mix-ramp",
                    "scale-stress", "multi-tenant")
SMOKE_FAMILIES = ("flash-crowd", "scale-stress")


def _ewma_log_mse(X, y) -> float:
    """Score the online EWMA the way the autoscaler uses it: replay each
    example's history bins through a fresh forecaster, predict once."""
    import numpy as np

    from repro.forecast import EwmaForecaster
    errs = []
    for hist, target in zip(X, y):
        f = EwmaForecaster()
        for r in hist:
            f.observe_bin(float(r))
        pred, _conf = f.predict()
        errs.append((np.log1p(pred) - np.log1p(float(target))) ** 2)
    return float(np.mean(errs))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--families", help=f"default {','.join(DEFAULT_FAMILIES)}")
    ap.add_argument("--seeds", type=int, default=48,
                    help="scenario seeds 0..N-1 per family (seed %% 4 == 3 "
                         "is the validation split)")
    ap.add_argument("--jobs", type=int, default=None,
                    help="trace length per (family, seed); default = each "
                         "family's native size")
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--train-seed", type=int, default=0,
                    help="param-init / batch-order seed")
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="also persist trained params under DIR (default: "
                         "round-trip through a temp dir only)")
    ap.add_argument("--smoke", action="store_true",
                    help=f"small CI gate: {','.join(SMOKE_FAMILIES)}, 4 "
                         "seeds, 300-job traces, 60 steps")
    ap.add_argument("--out", default="FORECAST_eval.json")
    args = ap.parse_args(argv)

    try:
        import jax  # noqa: F401
    except ImportError:
        raise SystemExit(
            "scripts/forecast.py requires JAX (gate the call on "
            "`python -c 'import jax'`, as scripts/ci.sh does)")

    import numpy as np

    from repro.compile_cache import use_compile_cache
    from repro.forecast import Ar1Baseline, WindowConfig, make_dataset
    from repro.forecast import model as fmodel

    use_compile_cache()

    if args.smoke:
        families = tuple((args.families or ",".join(SMOKE_FAMILIES))
                         .split(","))
        seeds = range(min(args.seeds, 4))
        n_jobs = args.jobs or 300
        steps = min(args.steps, 60)
    else:
        families = tuple((args.families or ",".join(DEFAULT_FAMILIES))
                         .split(","))
        seeds = range(args.seeds)
        n_jobs = args.jobs
        steps = args.steps

    window = WindowConfig()
    t0 = time.perf_counter()
    data = make_dataset(families, seeds, window, n_jobs=n_jobs)
    t_data = time.perf_counter() - t0
    print(f"dataset: train={data['X_train'].shape[0]} "
          f"val={data['X_val'].shape[0]} examples "
          f"({len(families)} families x {len(seeds)} seeds, {t_data:.1f}s)")

    t0 = time.perf_counter()
    result = fmodel.train_forecaster(
        data["X_train"], data["y_train"], window=window,
        X_val=data["X_val"], y_val=data["y_val"],
        seed=args.train_seed, steps=steps, d_model=args.d_model)
    t_train = time.perf_counter() - t0

    first = float(np.mean(result.losses[:10]))
    last = float(np.mean(result.losses[-10:]))
    ewma_mse = _ewma_log_mse(data["X_val"], data["y_val"])
    ar1 = Ar1Baseline.fit(data["X_train"], data["y_train"])
    ar1_mse = float(np.mean(
        (np.log1p(np.maximum(ar1.predict_batch(data["X_val"]), 0.0))
         - np.log1p(data["y_val"])) ** 2))

    # Checkpoint round-trip through the shared manager: saved params must
    # reload into a LearnedForecaster that accepts the online contract.
    ckpt_dir = args.ckpt or os.path.join(
        tempfile.mkdtemp(prefix="forecast_ckpt_"), "forecast")
    fmodel.save_forecaster(ckpt_dir, result, step=steps)
    restored = fmodel.load_forecaster(ckpt_dir)
    for r in data["X_val"][0] if data["X_val"].shape[0] else []:
        restored.observe_bin(float(r))
    rate, conf = restored.predict()
    print(f"train: loss {first:.4f} -> {last:.4f} over {steps} steps "
          f"({t_train:.1f}s); reload predict=({rate:.3f}, conf={conf:.2f})")
    print(f"val log-MSE: mlstm={result.val_mse:.4f} ewma={ewma_mse:.4f} "
          f"ar1={ar1_mse:.4f}")

    report = {
        "bench": "forecast_eval",
        "generated_unix_s": int(time.time()),
        "config": {"families": list(families), "seeds": len(seeds),
                   "n_jobs": n_jobs, "steps": steps,
                   "d_model": args.d_model, "train_seed": args.train_seed,
                   "window": {"bin_s": window.bin_s,
                              "history_bins": window.history_bins,
                              "horizon_bins": window.horizon_bins}},
        "dataset": {"n_train": int(data["X_train"].shape[0]),
                    "n_val": int(data["X_val"].shape[0])},
        "train": {"loss_first10": round(first, 6),
                  "loss_last10": round(last, 6),
                  "wall_s": round(t_train, 3)},
        "val_log_mse": {"mlstm": round(result.val_mse, 6),
                        "ewma": round(ewma_mse, 6),
                        "ar1": round(ar1_mse, 6)},
        "reload_predict": {"rate": round(rate, 6), "conf": round(conf, 6)},
        "checkpoint": ckpt_dir if args.ckpt else None,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {args.out}")

    assert last < first, (
        f"training loss did not decrease: {first:.4f} -> {last:.4f}")
    return report


if __name__ == "__main__":
    main()
