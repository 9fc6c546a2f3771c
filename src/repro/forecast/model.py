"""Learned rate forecaster: tiny mLSTM trunk on the jax_pallas substrate.

Reuses the repo's existing training machinery end to end — parameters
come from `repro.models.params.init_params` over `mlstm_specs`, the
optimizer is the in-house AdamW (`repro.train.optimizer`), and trained
params persist through `repro.train.checkpoint.CheckpointManager` — so
the forecaster is a (very small) citizen of the same world as the LM
configs rather than a parallel stack.

The model predicts the next-window mean arrival rate from
``history_bins`` past rates, in ``log1p`` space (rates are nonnegative
and heavy-tailed across the scenario families; squared error in log
space stops flash-crowd peaks from drowning the quiet regimes).

This module is the only JAX-importing part of `repro.forecast`; import
it lazily (`from repro.forecast import model`) so the numpy-only pieces
keep working where JAX is absent.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec, init_params
from repro.models.xlstm import apply_mlstm, mlstm_specs
from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state

from repro.forecast.baseline import _EPS
from repro.forecast.features import WindowConfig


def forecast_arch(d_model: int = 32, num_heads: int = 2) -> ArchConfig:
    """A minimal ArchConfig carrying just what `mlstm_specs` reads
    (d_model / proj_factor / num_heads / conv_width); the LM-only fields
    are inert placeholders."""
    return ArchConfig(name="rate-mlstm", family="ssm", num_layers=1,
                      d_model=d_model, num_heads=num_heads,
                      num_kv_heads=num_heads, d_ff=2 * d_model, vocab_size=0)


def forecast_specs(cfg: ArchConfig) -> Dict:
    return {
        "w_in": ParamSpec((1, cfg.d_model), ("embed", "rnn")),
        "block": mlstm_specs(cfg),
        "w_out": ParamSpec((cfg.d_model, 1), ("rnn", "embed"), scale=0.1),
        "b_out": ParamSpec((1,), (None,), init="zeros"),
    }


def apply_forecast(params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x: (B, T) log1p-rates -> (B,) predicted log1p next-window rate."""
    h = x[..., None] @ params["w_in"]                   # (B, T, D)
    h = h + apply_mlstm(params["block"], h, cfg)        # residual trunk
    y = h[:, -1, :] @ params["w_out"] + params["b_out"]
    return y[:, 0]


@dataclasses.dataclass
class TrainResult:
    params: Dict
    arch: ArchConfig
    window: WindowConfig
    losses: np.ndarray            # per-step training loss
    val_mse: Optional[float]      # log-space MSE on the val split


def _batches(rng: np.random.Generator, n: int, batch: int, steps: int):
    for _ in range(steps):
        yield rng.integers(0, n, size=batch)


def make_train_step(arch: ArchConfig, opt_cfg: OptimizerConfig):
    """The jitted AdamW step on log-space squared error:
    ``(params, opt_state, xb, yb) -> (params, opt_state, loss)``."""
    def loss_fn(p, xb, yb):
        pred = apply_forecast(p, xb, arch)
        return jnp.mean((pred - yb) ** 2)

    @jax.jit
    def step(p, s, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        p, s, _ = adamw_update(opt_cfg, p, grads, s)
        return p, s, loss

    return step


def train_forecaster(X: np.ndarray, y: np.ndarray, *,
                     window: WindowConfig,
                     X_val: Optional[np.ndarray] = None,
                     y_val: Optional[np.ndarray] = None,
                     seed: int = 0, steps: int = 300, batch: int = 64,
                     d_model: int = 32, num_heads: int = 2,
                     learning_rate: float = 3e-3) -> TrainResult:
    """Fit the mLSTM forecaster on (X, y) rate examples.

    Deterministic for fixed inputs + hyperparameters: param init is keyed
    on ``seed``, batch order on the same seed's numpy stream, and every
    update is the jitted AdamW step."""
    if X.shape[0] == 0:
        raise ValueError("empty training set")
    arch = forecast_arch(d_model=d_model, num_heads=num_heads)
    params = init_params(jax.random.key(seed), forecast_specs(arch))
    opt_cfg = OptimizerConfig(learning_rate=learning_rate,
                              warmup_steps=max(1, steps // 10),
                              total_steps=steps, weight_decay=0.0)
    opt_state = init_opt_state(params)
    step = make_train_step(arch, opt_cfg)
    Xl = np.log1p(np.asarray(X, np.float32))
    yl = np.log1p(np.asarray(y, np.float32))
    rng = np.random.default_rng(seed)
    losses = []
    for idx in _batches(rng, Xl.shape[0], min(batch, Xl.shape[0]), steps):
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(Xl[idx]),
                                       jnp.asarray(yl[idx]))
        losses.append(float(loss))

    val_mse = None
    if X_val is not None and X_val.shape[0]:
        pred = apply_forecast(params, jnp.asarray(
            np.log1p(np.asarray(X_val, np.float32))), arch)
        val_mse = float(jnp.mean(
            (pred - jnp.asarray(np.log1p(np.asarray(y_val, np.float32))))
            ** 2))
    return TrainResult(params=params, arch=arch, window=window,
                       losses=np.asarray(losses), val_mse=val_mse)


class LearnedForecaster:
    """Online wrapper giving trained params the baseline forecaster
    contract (`observe_bin` / `predict`, see repro.forecast.baseline).

    Inference is a single jitted apply over the last ``history_bins``
    rates — deterministic for fixed params and history.  Confidence uses
    the same EW one-step-error convention as `EwmaForecaster`, seeded at
    full trust once enough history has accumulated."""

    name = "mlstm"

    def __init__(self, params, arch: ArchConfig, window: WindowConfig,
                 err_alpha: float = 0.25):
        self.params = params
        self.arch = arch
        self.window = window
        self.err_alpha = err_alpha
        self._hist = collections.deque(maxlen=window.history_bins)
        self._mae = 0.0
        self._last_pred: Optional[float] = None
        self._apply = jax.jit(
            lambda p, x: apply_forecast(p, x, arch))

    def observe_bin(self, rate: float) -> None:
        rate = float(rate)
        if self._last_pred is not None:
            self._mae += self.err_alpha * (abs(rate - self._last_pred)
                                           - self._mae)
        self._hist.append(rate)

    def predict(self) -> Tuple[float, float]:
        if len(self._hist) < self.window.history_bins:
            return 0.0, 0.0
        x = jnp.asarray(np.log1p(np.asarray(self._hist, np.float32)))[None]
        rate = float(np.expm1(np.asarray(self._apply(self.params, x))[0]))
        rate = max(0.0, rate)
        self._last_pred = rate
        conf = 1.0 / (1.0 + self._mae / (rate + _EPS))
        return rate, conf


# -- checkpoint round-trip ----------------------------------------------------

def save_forecaster(directory: str, result: TrainResult, step: int) -> str:
    """Persist trained params + geometry with the shared CheckpointManager
    (leaves.npz + meta.json, atomic keep-N — same format as the trainers)."""
    from repro.train.checkpoint import CheckpointManager
    extra = {"d_model": result.arch.d_model,
             "num_heads": result.arch.num_heads,
             "bin_s": result.window.bin_s,
             "history_bins": result.window.history_bins,
             "horizon_bins": result.window.horizon_bins}
    return CheckpointManager(directory).save(step, result.params, extra=extra)


def load_forecaster(directory: str,
                    step: Optional[int] = None) -> LearnedForecaster:
    from repro.train.checkpoint import CheckpointManager
    mgr = CheckpointManager(directory)
    found = mgr.latest_step() if step is None else step
    if found is None:
        raise FileNotFoundError(f"no forecaster checkpoint in {directory}")
    d = mgr.directory
    import json
    import os
    with open(os.path.join(d, f"step_{found:08d}", "meta.json")) as f:
        extra = json.load(f)["extra"]
    arch = forecast_arch(d_model=int(extra["d_model"]),
                         num_heads=int(extra["num_heads"]))
    like = jax.tree.map(lambda s: np.zeros(s.shape, np.float32),
                        forecast_specs(arch),
                        is_leaf=lambda s: isinstance(s, ParamSpec))
    params, _, _ = mgr.restore(like, step=found)
    window = WindowConfig(bin_s=float(extra["bin_s"]),
                          history_bins=int(extra["history_bins"]),
                          horizon_bins=int(extra["horizon_bins"]))
    return LearnedForecaster(params, arch, window)
