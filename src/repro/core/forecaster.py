"""Workload forecasters — the PPA's injectable predictive models, in pure JAX.

The paper evaluates statsmodels ARMA(1,1,1) (= ARIMA with one difference) and
a Keras LSTM(50)+ReLU-dense model.  Both are reimplemented here as jit'd JAX
programs following the model protocol of §4.2.2: input = the last ``window``
rows of [CPU, RAM, NetIn, NetOut, Custom], output = the next row.  A deep
ensemble wrapper provides the Bayesian confidence path of Algorithm 1.

All forecasters implement:
    fit(series (T, M), from_scratch=bool)   — (re)train
    predict(recent (W, M)) -> (mean (M,), std (M,) | None)
    predict_batch(recents (Z, T, M)) -> (means (Z, M), stds (Z, M) | None)
    valid() / is_bayesian / save(path) / load(path)

``predict_batch`` is the batched control plane's hot path (DESIGN.md §5):
one model serving Z scaling targets answers all of them in a single device
dispatch.  With ``use_pallas=True`` that dispatch is the fused
block-batched sequence kernel (``kernels/lstm_seq.py``, DESIGN.md §7):
the whole W-step window runs inside ONE kernel with (h, c) resident in
VMEM scratch, for both the shared-weights layout (``lstm_forward``) and
the stacked per-target layout (``_lstm_forward_stacked`` — Z independently
trained LSTMs, per-row GEMV gate matmuls).  The kernel carries a
checkpoint-style custom VJP, so the fit paths differentiate through it.

The batched forecasts run their programs through ``faults.Staged``: a
forward that cannot be built raises ``ProgramFault``, which the control
plane never serves as a reactive tick.
"""
from __future__ import annotations

import functools
import pickle
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import Staged
from repro.core.metrics import N_METRICS
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update


# ------------------------------------------------------------------ base ---
class Forecaster:
    window: int = 1
    is_bayesian: bool = False

    def fit(self, series: np.ndarray, from_scratch: bool = False): ...
    def predict(self, recent: np.ndarray): ...
    def valid(self) -> bool: return True

    def predict_batch(self, recents):
        """recents: (Z, T, M) array or length-Z list of (T, M) windows ->
        (means (Z, M), stds (Z, M) | None).  Base implementation loops
        ``predict``; subclasses override with a truly batched path."""
        means, stds = [], []
        for r in recents:
            mean, std = self.predict(np.asarray(r))
            means.append(mean)
            stds.append(std)
        batched_std = (np.stack(stds) if all(s is not None for s in stds)
                       else None)
        return np.stack(means), batched_std

    def save(self, path):
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        with open(path, "wb") as f:
            pickle.dump(self.__getstate__(), f)

    def load(self, path):
        with open(path, "rb") as f:
            self.__setstate__(pickle.load(f))
        return self


# --------------------------------------------------------------- scaling ---
Z_CLIP = 10.0   # z-score clamp shared by every transform path


def transform_stacked(wins: np.ndarray, mean: np.ndarray, std: np.ndarray
                      ) -> np.ndarray:
    """``Scaler.transform`` broadcast over stacked per-target stats:
    wins (Z, W, M), mean/std (Z, M) -> (Z, W, M).  The vectorised control
    plane routes through this single definition so its arithmetic can
    never diverge from the scalar decision path."""
    return np.clip((wins - mean[:, None]) / std[:, None], -Z_CLIP, Z_CLIP)


class Scaler:
    """Per-metric standardisation (the paper's ScalerLink companion)."""

    def __init__(self):
        self.mean = np.zeros(N_METRICS)
        self.std = np.ones(N_METRICS)
        self.fitted = False

    def fit(self, series: np.ndarray):
        self.mean = series.mean(0)
        # relative floor: a constant training column (e.g. RAM with a fixed
        # replica count) must not blow up z-scores at serve time
        self.std = np.maximum(series.std(0), 0.01 * (np.abs(self.mean) + 1.0))
        self.fitted = True

    def transform(self, x):
        return np.clip((x - self.mean) / self.std, -Z_CLIP, Z_CLIP)
    def inverse(self, x):    return x * self.std + self.mean
    def inverse_std(self, s): return s * self.std


# ------------------------------------------------------------------ LSTM ---
# inits are jitted: one dispatch per model instead of one per random draw,
# which dominates building Z >= 10^4 models on a TPU host
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _lstm_init(key, n_in: int, hidden: int, n_out: int):
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / np.sqrt(hidden)
    return {
        "Wx": jax.random.normal(k1, (n_in, 4 * hidden)) * s,
        "Wh": jax.random.normal(k2, (hidden, 4 * hidden)) * s,
        "b": jnp.zeros((4 * hidden,)),
        "Wo": jax.random.normal(k3, (hidden, n_out)) * s,
        "bo": jnp.zeros((n_out,)),
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _attn_init(key, n_in: int, hidden: int, n_out: int):
    """Attention-Double-LSTM parameters: two LSTM layers bridged by a
    window-length temporal-attention block (query projection ``Wa``)."""
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(hidden)
    return {
        "Wx1": jax.random.normal(k1, (n_in, 4 * hidden)) * s,
        "Wh1": jax.random.normal(k2, (hidden, 4 * hidden)) * s,
        "b1": jnp.zeros((4 * hidden,)),
        "Wa": jax.random.normal(k3, (hidden, hidden)) * s,
        "Wx2": jax.random.normal(k4, (hidden, 4 * hidden)) * s,
        "Wh2": jax.random.normal(k5, (hidden, 4 * hidden)) * s,
        "b2": jnp.zeros((4 * hidden,)),
        "Wo": jax.random.normal(k6, (hidden, n_out)) * s,
        "bo": jnp.zeros((n_out,)),
    }


def _attn_body(params, xs):
    """Pure-jnp Attention-Double-LSTM forward: xs (B, W, M) -> (B, n_out).
    Op-for-op ``kernels/ref.attn_lstm_seq`` with dict params — the XLA
    (non-Pallas) serving/fit path of ``AttnLSTMForecaster``; the fused
    kernel's custom-VJP backward replays the same math, so both paths train
    with identical gradients.

    Stage 1: first LSTM scan keeping every hidden state; stage 2: temporal
    attention (query = final hidden state @ Wa, scaled-dot scores over the
    window, softmax weights reweight the hidden sequence); stage 3: second
    LSTM scan over the reweighted sequence + ReLU-dense head."""
    B = xs.shape[0]
    H = params["Wh1"].shape[-2]
    h = jnp.zeros((B, H))
    c = jnp.zeros((B, H))

    def step1(carry, x):
        h, c = carry
        gates = x @ params["Wx1"] + h @ params["Wh1"] + params["b1"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h1, _), hs = jax.lax.scan(step1, (h, c), jnp.swapaxes(xs, 0, 1))
    hs = jnp.swapaxes(hs, 0, 1)                          # (B, W, H)
    q = h1 @ params["Wa"]                                # (B, H)
    scores = jnp.sum(hs * q[:, None, :], axis=-1) * (H ** -0.5)
    alpha = jax.nn.softmax(scores, axis=-1)              # (B, W)
    ctx = alpha[:, :, None] * hs                         # reweighted sequence

    h = jnp.zeros((B, H))
    c = jnp.zeros((B, H))

    def step2(carry, a):
        h, c = carry
        gates = a @ params["Wx2"] + h @ params["Wh2"] + params["b2"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    (h2, _), _ = jax.lax.scan(step2, (h, c), jnp.swapaxes(ctx, 0, 1))
    return jax.nn.relu(h2) @ params["Wo"] + params["bo"]


# architecture registry: arch name -> (param init, ordered leaf names).
# ``arch`` is threaded as a STATIC argument through every jitted forward /
# fit below, so one function tree serves the whole forecaster zoo — adding
# an architecture means an init + a forward body + one entry here, not a
# parallel copy of the stacking/fit/device-residency protocol.
ARCH_INITS = {"lstm": _lstm_init, "attn": _attn_init}
ARCH_PARAM_LEAVES = {
    "lstm": ("Wx", "Wh", "b", "Wo", "bo"),
    "attn": ("Wx1", "Wh1", "b1", "Wa", "Wx2", "Wh2", "b2", "Wo", "bo"),
}


def lstm_cell(params, h, c, x):
    """One LSTM step, pure jnp.  x (..., n_in); h, c (..., H).  The Pallas
    path no longer routes through here: ``use_pallas=True`` dispatches the
    whole window to the fused sequence kernel in ``lstm_forward`` (the
    single-step ``kernels/ops.lstm_cell`` remains for the bench's legacy
    comparison lane)."""
    gates = x @ params["Wx"] + h @ params["Wh"] + params["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


@functools.partial(jax.jit, static_argnames=("use_pallas", "arch"))
def lstm_forward(params, xs, *, use_pallas: bool = False,
                 arch: str = "lstm"):
    """xs (B, W, M) -> prediction (B, M).

    ``use_pallas=True`` routes through the fused whole-window sequence
    kernel (``kernels/lstm_seq.py`` for ``arch="lstm"``,
    ``kernels/attn_lstm_seq.py`` for ``arch="attn"``): one dispatch keeps
    (h, c) — and for attn the whole hidden-state history + attention —
    resident in VMEM scratch across the W timesteps instead of re-launching
    a cell kernel per scan step.  Both kernels are differentiable
    (checkpoint-style custom VJP replaying the jnp reference), so every
    fit-path forward rides them too."""
    if arch == "attn":
        if use_pallas:
            from repro.kernels import ops
            return ops.attn_lstm_seq(
                params["Wx1"], params["Wh1"], params["b1"], params["Wa"],
                params["Wx2"], params["Wh2"], params["b2"],
                params["Wo"], params["bo"], xs)
        return _attn_body(params, xs)
    if use_pallas:
        from repro.kernels import ops
        return ops.lstm_seq(params["Wx"], params["Wh"], params["b"],
                            params["Wo"], params["bo"], xs)
    B = xs.shape[0]
    H = params["Wh"].shape[0]
    h = jnp.zeros((B, H))
    c = jnp.zeros((B, H))

    def step(carry, x):
        h, c = carry
        h, c = lstm_cell(params, h, c, x)
        return (h, c), None

    (h, c), _ = jax.lax.scan(step, (h, c), jnp.swapaxes(xs, 0, 1))
    return jax.nn.relu(h) @ params["Wo"] + params["bo"]


lstm_forward_staged = Staged(lstm_forward)


@functools.partial(jax.jit, static_argnames=("opt_cfg", "epochs",
                                             "use_pallas", "arch"))
def _lstm_fit(params, opt_state, X, Y, opt_cfg, epochs, use_pallas=False,
              arch="lstm"):
    def loss_fn(p):
        pred = lstm_forward(p, X, use_pallas=use_pallas, arch=arch)
        return jnp.mean((pred - Y) ** 2)

    def epoch(carry, _):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, _ = adamw_update(grads, opt_state, params, opt_cfg)
        return (params, opt_state), loss

    (params, opt_state), losses = jax.lax.scan(
        epoch, (params, opt_state), None, length=epochs)
    return params, opt_state, losses


class LSTMForecaster(Forecaster):
    """Paper §5.3.1: LSTM(50) + ReLU dense head, MSE loss, Adam.

    ``residual=True`` regresses the per-step delta (prediction = last value +
    net output) — the net degrades to persistence when uncertain, which keeps
    it robust when the serving regime drifts from the collection regime.

    ``arch``/``PARAM_LEAVES`` are the class's entry in the architecture
    registry: every stacked-protocol consumer (stack signature, batched
    fits, the device plane's weight cache) keys on them instead of on the
    concrete class, so subclasses that swap the forward body
    (``AttnLSTMForecaster``) inherit the whole protocol."""

    arch: str = "lstm"
    PARAM_LEAVES: tuple = ARCH_PARAM_LEAVES["lstm"]

    def __init__(self, window: int = 1, hidden: int = 50, epochs: int = 150,
                 finetune_epochs: int = 30, lr: float = 1e-2, seed: int = 0,
                 residual: bool = True, use_pallas: bool = False):
        self.window, self.hidden = window, hidden
        self.epochs, self.finetune_epochs = epochs, finetune_epochs
        self.residual = residual
        self.use_pallas = use_pallas
        self.opt_cfg = AdamWConfig(lr=lr, weight_decay=0.0, clip_norm=None,
                                   warmup_steps=0, total_steps=10**9,
                                   min_lr_ratio=1.0)
        self._seed = seed
        self.params = self._init_params(jax.random.PRNGKey(seed))
        self.scaler = Scaler()
        self._fitted = False
        self._fit_count = 0   # generation counter (stacked-batch cache key)

    def _init_params(self, key):
        return ARCH_INITS[self.arch](key, N_METRICS, self.hidden, N_METRICS)

    def _windows(self, series):
        z = self.scaler.transform(series)
        W = self.window
        X = np.stack([z[i:i + W] for i in range(len(z) - W)])
        Y = z[W:] - z[W - 1:-1] if self.residual else z[W:]
        return jnp.asarray(X), jnp.asarray(Y)

    def fit(self, series: np.ndarray, from_scratch: bool = False):
        if len(series) < self.window + 8:
            return self
        if from_scratch or not self._fitted:
            self.scaler.fit(series)
            # the model's own seed, not a shared constant: ensemble members
            # refit from scratch must stay diverse (the Bayesian std path)
            self.params = self._init_params(
                jax.random.PRNGKey(getattr(self, "_seed", 0)))
            epochs = self.epochs
        else:
            epochs = self.finetune_epochs
        X, Y = self._windows(series)
        opt = adamw_init(self.params, self.opt_cfg)
        self.params, _, losses = _lstm_fit(self.params, opt, X, Y,
                                           self.opt_cfg, epochs,
                                           self.use_pallas, self.arch)
        self._fitted = True
        self._fit_count += 1
        self.last_losses = np.asarray(losses)
        return self

    def predict(self, recent: np.ndarray):
        if not self._fitted:
            raise RuntimeError("model not fitted")
        z = self.scaler.transform(recent[-self.window:])
        pred = lstm_forward(self.params, jnp.asarray(z)[None],
                            use_pallas=self.use_pallas, arch=self.arch)[0]
        pred = np.asarray(pred)
        if self.residual:
            pred = z[-1] + pred
        return self.scaler.inverse(pred), None

    def predict_batch(self, recents):
        """One device dispatch for Z targets sharing this model: the window
        batch (Z, W, M) rides ``lstm_forward``'s batch axis (which the
        Pallas kernel tiles), instead of Z separate dispatches.  The scaler
        transform is broadcast over the whole batch (one numpy program, not
        Z per-target calls) — elementwise identical to per-target
        ``transform``."""
        if not self._fitted:
            raise RuntimeError("model not fitted")
        if isinstance(recents, np.ndarray) and recents.ndim == 3:
            wins = np.asarray(recents, np.float64)[:, -self.window:]
        else:
            wins = np.stack([np.asarray(r, np.float64)[-self.window:]
                             for r in recents])
        z = self.scaler.transform(wins)
        pred = np.asarray(lstm_forward_staged(self.params, jnp.asarray(z),
                                              use_pallas=self.use_pallas,
                                              arch=self.arch))
        if self.residual:
            pred = z[:, -1] + pred
        return self.scaler.inverse(pred), None

    def valid(self):
        if not self._fitted:
            return False
        # params only change on fit — memoize the finiteness sweep per fit
        # generation (it is a control-plane per-tick hot path)
        cached = getattr(self, "_valid_cache", None)
        if cached is not None and cached[0] == self._fit_count:
            return cached[1]
        ok = all(bool(np.isfinite(np.asarray(v)).all())
                 for v in jax.tree.leaves(self.params))
        self._valid_cache = (self._fit_count, ok)
        return ok

    def __getstate__(self):
        d = dict(self.__dict__)
        d["params"] = jax.tree.map(np.asarray, self.params)
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self.params = jax.tree.map(jnp.asarray, d["params"])


class AttnLSTMForecaster(LSTMForecaster):
    """Attention-Double-LSTM (PAPERS.md, "Mitigating Temporal Blindness in
    Kubernetes Autoscaling"): a first LSTM encodes the window, temporal
    attention over its hidden states re-weights the sequence, and a second
    LSTM + ReLU-dense head reads the re-weighted context.  The attention
    lets the model key on burst onsets anywhere in the window, where the
    plain LSTM's single final hidden state is "temporally blind" on
    bursty / serverless traces.

    Everything else — the stacked per-target protocol, batched fits, the
    device plane's epoch-keyed weight cache, the fused Pallas serving path
    (``kernels/attn_lstm_seq.py``) — is inherited via the ``arch``
    registry; this class only swaps the architecture entry and the default
    window (attention needs history to attend over)."""

    arch = "attn"
    PARAM_LEAVES = ARCH_PARAM_LEAVES["attn"]

    def __init__(self, window: int = 8, **kw):
        super().__init__(window=window, **kw)


# ----------------------------------------------------- stacked batching ---
def lstm_stack_signature(m: "LSTMForecaster") -> tuple:
    """The architecture attributes that must match for params to stack on
    one leading axis — the single definition every stackability check uses
    (fitting additionally requires a matching ``opt_cfg``).  Leads with
    ``arch`` so different forward bodies (lstm vs attn) can never stack
    into one dispatch."""
    return (m.arch, m.window, m.hidden, m.residual, m.use_pallas)


def stack_params(models) -> dict:
    """Stack Z models' parameter pytrees on a new leading axis — the
    one construction every stacked-batch cache (per-target, fused, member)
    shares; each cache keeps its own invalidation key.  The stack happens
    in host numpy (one upload of the stacked leaf), not as a Z-operand
    XLA concatenate — at Z >= 10^4 jnp.stack would hand the compiler tens
    of thousands of operands."""
    return jax.tree.map(
        lambda *leaves: jnp.asarray(np.stack([np.asarray(x) for x in leaves])),
        *[m.params for m in models])


def stack_scaler_stats(models) -> tuple[np.ndarray, np.ndarray]:
    """(mean (Z, M), std (Z, M)) stacks for ``transform_stacked``."""
    return (np.stack([m.scaler.mean for m in models]),
            np.stack([m.scaler.std for m in models]))


def stacked_operands(leaf, window: int, *, use_pallas: bool = False,
                     arch: str = "lstm", xp=jnp):
    """The weight operands ``stacked_forward`` reads at ``window``, from
    stacked per-target leaves: ``leaf(name)`` gives the leaf of that name
    with its leading target axis.  The fused LSTM kernel reads its
    ``stacked_form`` (``kernels/lstm_seq.py``: at window 1 one row of
    128-lane-aligned blocks per target and no ``Wh``); every other
    forward reads the leaves as they are.  ``xp`` is ``np`` where the operands are built on the
    host (the device plane's install) and ``jnp`` inside a program."""
    if arch == "lstm" and use_pallas:
        from repro.kernels.lstm_seq import stacked_form
        return stacked_form(leaf, window, xp=xp)
    return {name: leaf(name) for name in ARCH_PARAM_LEAVES[arch]}


def stacked_forward(operands, xs, *, use_pallas: bool = False,
                    arch: str = "lstm"):
    """Pure (unjitted) stacked per-target forward body: the operands of
    ``stacked_operands`` (leading target axis Z), xs (Z, W, M) -> (Z, M).
    Split out of ``_lstm_forward_stacked`` so callers that build their own
    dispatch wrapper — the device plane's ``jax.jit``/``shard_map``
    programs (core/device_plane.py), which install the operands once per
    refit epoch — trace the SAME math instead of nesting jits.
    The Pallas path routes through ``ops.lstm_seq_stacked_local`` /
    ``ops.attn_lstm_seq_stacked_local`` (the shard_map-compatible entries:
    local block shapes, no jit boundary).

    Both LSTM paths elide the first timestep's recurrent terms: with
    h0 = c0 = 0 the ``h @ Wh`` matmul and the ``sigmoid(f) * c`` forget
    term are exactly zero, so step 1 reduces to the input projection —
    at window=1 (the forecaster default) that removes the dominant
    batched GEMV from the whole dispatch, and the kernel's operands hold
    no ``Wh``.  The elision is value-exact (identical at window=1; later
    steps of the XLA path may differ from the scan-only graph at f32
    fusion-rounding level, within forecast parity tolerances).  The
    training path (``lstm_forward``) keeps the plain scan so fit losses
    and gradients are untouched."""
    if arch == "attn":
        if use_pallas:
            from repro.kernels import ops
            return ops.attn_lstm_seq_stacked_local(
                operands["Wx1"], operands["Wh1"], operands["b1"],
                operands["Wa"], operands["Wx2"], operands["Wh2"],
                operands["b2"], operands["Wo"], operands["bo"], xs)
        return jax.vmap(lambda p, x: _attn_body(p, x[None])[0])(
            operands, xs)
    if use_pallas:
        from repro.kernels import ops
        return ops.lstm_seq_stacked_local(operands, xs)

    def fwd(p, x):
        gates = x[0] @ p["Wx"] + p["b"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        if x.shape[0] > 1:
            def step(carry, xw):
                h, c = carry
                return lstm_cell(p, h, c, xw), None
            (h, c), _ = jax.lax.scan(step, (h, c), x[1:])
        return jax.nn.relu(h) @ p["Wo"] + p["bo"]
    return jax.vmap(fwd)(operands, xs)


@functools.partial(jax.jit, static_argnames=("use_pallas", "arch"))
def _lstm_forward_stacked(stacked_params, xs, *, use_pallas: bool = False,
                          arch: str = "lstm"):
    """stacked_params: pytree with leading target axis Z; xs (Z, W, M) ->
    (Z, M).  One device dispatch for all Z targets: the Pallas path is the
    fused block-batched sequence kernel (per-row weights in the stacked
    form, built here per call; W-step fori_loop in VMEM scratch); the XLA
    path vmaps the scan forward."""
    operands = stacked_operands(stacked_params.__getitem__, xs.shape[1],
                                use_pallas=use_pallas, arch=arch)
    return stacked_forward(operands, xs, use_pallas=use_pallas, arch=arch)


forward_stacked_staged = Staged(_lstm_forward_stacked)


def lstm_predict_batch_stacked(models: list["LSTMForecaster"], recents,
                               cache: dict | None = None):
    """Batched forecast across Z *independently trained* per-target LSTMs:
    stack the parameter pytrees on a new leading axis and vmap the forward —
    one device dispatch instead of Z (core/controller.py's per-target
    mode).  Models must share architecture/window/residual settings.

    Stacking + host->device upload dominates the tick cost, so pass a
    ``cache`` dict to reuse the stacked pytree across ticks; it is re-stacked
    only when a model is (re)fit (tracked via each model's fit generation).
    """
    m0 = models[0]
    sig = lstm_stack_signature(m0)
    if not all(lstm_stack_signature(m) == sig for m in models):
        raise ValueError("stacked batching needs homogeneous models")
    z = np.stack([m.scaler.transform(np.asarray(r, np.float64)[-m0.window:])
                  for m, r in zip(models, recents)])
    key = tuple((id(m), getattr(m, "_fit_count", 0)) for m in models)
    if cache is not None and cache.get("key") == key:
        stacked = cache["stacked"]
    else:
        stacked = stack_params(models)
        if cache is not None:
            cache["key"] = key
            cache["stacked"] = stacked
            # hold strong refs: id() keys are only unique while the models
            # they were taken from stay alive (address reuse after gc would
            # otherwise let a fresh model hit a stale cache entry)
            cache["models"] = list(models)
    preds = np.asarray(forward_stacked_staged(stacked, jnp.asarray(z),
                                              use_pallas=m0.use_pallas,
                                              arch=m0.arch))
    if m0.residual:
        preds = z[:, -1] + preds
    means = np.stack([m.scaler.inverse(p)
                      for m, p in zip(models, preds)])
    return means, None


@functools.partial(jax.jit, static_argnames=("opt_cfg", "epochs",
                                             "use_pallas", "arch"))
def _lstm_fit_stacked(stacked_params, stacked_opt, X, Y, opt_cfg, epochs,
                      use_pallas=False, arch="lstm"):
    """Fit Z independently parameterised models in ONE dispatch: params/opt
    state stacked on a leading target axis, X (Z, N, W, M), Y (Z, N, M);
    vmap of the scalar ``_lstm_fit`` epoch scan."""
    def fit_one(p, o, x, y):
        return _lstm_fit(p, o, x, y, opt_cfg, epochs, use_pallas, arch)
    return jax.vmap(fit_one)(stacked_params, stacked_opt, X, Y)


@functools.partial(jax.jit, static_argnames=("opt_cfg", "epochs",
                                             "use_pallas", "arch"))
def _lstm_fit_stacked_masked(stacked_params, stacked_opt, X, Y, W, opt_cfg,
                             epochs, use_pallas=False, arch="lstm"):
    """``_lstm_fit_stacked`` with a per-window weight mask ``W`` (Z, N):
    ragged histories pad their window batches to a common N and zero the
    padding's loss weight, so unequal-length targets still refit in ONE
    vmapped dispatch.  With ``W[i] = 1`` on the real windows the weighted
    loss equals the unpadded per-target MSE exactly, so gradients (and the
    whole epoch scan) match the sequential fit."""
    def fit_one(p, o, x, y, w):
        def loss_fn(pp):
            pred = lstm_forward(pp, x, use_pallas=use_pallas, arch=arch)
            se = jnp.sum(w[:, None] * (pred - y) ** 2)
            return se / (jnp.sum(w) * y.shape[-1])

        def epoch(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state, _ = adamw_update(grads, opt_state, params,
                                                opt_cfg)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            epoch, (p, o), None, length=epochs)
        return params, opt_state, losses
    return jax.vmap(fit_one)(stacked_params, stacked_opt, X, Y, W)


class BatchFitResult:
    """Deferred application of a batched fit.

    The device compute happens at construction (``lstm_fit_batch_stacked``);
    ``apply()`` installs the new params / scalers / fit counters on the
    models.  The split exists for the async control plane: ``compute`` runs
    on a worker thread without mutating any model, ``apply`` runs on the
    control thread between ticks, so an in-flight forecast never reads a
    half-updated model.
    """

    def __init__(self):
        self._groups: list[tuple] = []   # (models, scalers, params, losses)

    def add(self, models, scalers, stacked_params, losses):
        self._groups.append((models, scalers, stacked_params, losses))

    def block_until_ready(self):
        for _, _, stacked, _ in self._groups:
            jax.tree.leaves(stacked)[0].block_until_ready()
        return self

    def apply(self):
        for models, scalers, stacked, losses in self._groups:
            losses = np.asarray(losses)
            # one device read per leaf; each model keeps host views into
            # it (slicing on the device would be Z x leaves dispatches)
            stacked = jax.tree.map(np.asarray, stacked)
            for i, m in enumerate(models):
                m.scaler = scalers[i]
                m.params = jax.tree.map(lambda leaf, i=i: leaf[i], stacked)
                m._fitted = True
                m._fit_count += 1
                m._valid_cache = None
                m.last_losses = losses[i]
        return self


def lstm_fit_batch_stacked(models: list["LSTMForecaster"], serieses,
                           from_scratch: bool = False, apply: bool = True):
    """Batched counterpart of Z sequential ``LSTMForecaster.fit`` calls:
    stack the parameter pytrees and training windows on a leading target
    axis and vmap the whole epoch scan — P2/P3 refits of all Z targets are
    one jitted dispatch instead of Z (the Updater cadence item, DESIGN.md
    §5).

    Preconditions for stacking: homogeneous architecture (window / hidden /
    residual / use_pallas / opt_cfg).  Unequal-length histories stay on the
    vmapped path via pad-and-mask (``_lstm_fit_stacked_masked``): each
    group's window batches are zero-padded to the longest target and the
    padding carries zero loss weight, so ragged fits match their sequential
    counterparts.  A list of ``EnsembleForecaster``s is flattened to its
    members (E members x Z targets on the one batch axis).  Returns
    ``None`` only when the models genuinely can't stack (heterogeneous
    architectures / non-LSTM types) — the caller falls back to sequential
    fits.  Otherwise returns a ``BatchFitResult`` (already applied unless
    ``apply=False``; models needing full-epoch scratch training and models
    needing finetune epochs are grouped, one dispatch per group — a single
    dispatch in the homogeneous steady state).
    """
    if models and all(type(m) is EnsembleForecaster for m in models):
        # E x Z: every ensemble's members ride the same stacked batch axis,
        # each member fitting on its ensemble's series
        flat = [mm for m in models for mm in m.members]
        flat_series = [s for m, s in zip(models, serieses)
                       for _ in m.members]
        return lstm_fit_batch_stacked(flat, flat_series, from_scratch,
                                      apply)
    if not models or not all(isinstance(m, LSTMForecaster) for m in models):
        return None
    m0 = models[0]
    sig = lstm_stack_signature(m0) + (m0.opt_cfg,)
    if not all(lstm_stack_signature(m) + (m.opt_cfg,) == sig
               for m in models):
        return None
    serieses = [np.asarray(s, np.float64) for s in serieses]
    if len({s.shape[1:] for s in serieses}) != 1:
        return None                      # metric dimension must agree
    result = BatchFitResult()
    W = m0.window
    # fit()'s minimum-history gate, per target: short histories no-op
    # sequentially, so they are simply excluded from the batch
    eligible = [(m, s) for m, s in zip(models, serieses)
                if len(s) >= W + 8]
    if not eligible:
        return result.apply() if apply else result
    groups: dict[tuple, list[tuple]] = defaultdict(list)
    for m, s in eligible:
        scratch = from_scratch or not m._fitted
        groups[(m.epochs if scratch else m.finetune_epochs,
                scratch)].append((m, s))
    for (epochs, scratch), pairs in groups.items():
        ms, Xs, Ys, scalers = [], [], [], []
        for m, s in pairs:
            if scratch:
                sc = Scaler()
                sc.fit(s)
            else:
                sc = m.scaler
            z = sc.transform(s)
            Xs.append(np.stack([z[i:i + W] for i in range(len(z) - W)]))
            Ys.append(z[W:] - z[W - 1:-1] if m.residual else z[W:])
            ms.append(m)
            scalers.append(sc)
        # params and optimiser state are built stacked, a few dispatches
        # for the whole group rather than a few per model
        if scratch:
            # each model's own seed, as fit() would use it
            seeds = jnp.asarray([getattr(m, "_seed", 0) for m in ms])
            stacked_p = jax.vmap(
                lambda seed: m0._init_params(jax.random.PRNGKey(seed)))(seeds)
        else:
            stacked_p = stack_params(ms)
        stacked_o = jax.vmap(lambda p: adamw_init(p, m0.opt_cfg))(stacked_p)
        lens = {len(x) for x in Xs}
        static = (m0.opt_cfg, epochs, m0.use_pallas, m0.arch)
        if len(lens) == 1:
            new_p, losses = _fit_in_chunks(
                _lstm_fit_stacked,
                (stacked_p, stacked_o, jnp.asarray(np.stack(Xs)),
                 jnp.asarray(np.stack(Ys))), static)
        else:
            # ragged: pad to the longest window batch, mask the padding
            n_max = max(lens)
            Xp = np.zeros((len(Xs), n_max) + Xs[0].shape[1:])
            Yp = np.zeros((len(Ys), n_max) + Ys[0].shape[1:])
            Wt = np.zeros((len(Xs), n_max))
            for i, (x, y) in enumerate(zip(Xs, Ys)):
                Xp[i, :len(x)] = x
                Yp[i, :len(y)] = y
                Wt[i, :len(x)] = 1.0
            new_p, losses = _fit_in_chunks(
                _lstm_fit_stacked_masked,
                (stacked_p, stacked_o, jnp.asarray(Xp), jnp.asarray(Yp),
                 jnp.asarray(Wt)), static)
        result.add(ms, scalers, new_p, losses)
    return result.apply() if apply else result


def _device_bytes_free() -> int | None:
    """Bytes the default device can still allocate, where its backend
    reports memory (a TPU does; the CPU does not)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def _fit_in_chunks(fit, arrays, static, probe: int = 256):
    """Run a stacked fit program over its leading target axis in as few
    equal chunks as the device's free memory holds; returns ``(params,
    losses)``.  The footprint per target is read from the program
    compiled for a probe of ``probe`` targets; a backend that reports no
    memory runs one dispatch.  Targets are independent rows, so the split
    changes no target's result."""
    n = len(arrays[2])
    k = n
    free = _device_bytes_free()
    if free is not None and n > probe:
        mem = fit.lower(*jax.tree.map(lambda a: a[:probe], arrays),
                        *static).compile().memory_analysis()
        if mem is not None:
            per_target = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                          + mem.output_size_in_bytes) / probe
            k = max(1, min(n, int(0.8 * free / per_target)))
    k = -(-n // -(-n // k))              # equal chunks of at most k
    outs = [fit(*jax.tree.map(lambda a: a[i:i + k], arrays), *static)
            for i in range(0, n, k)]
    if len(outs) == 1:
        return outs[0][0], outs[0][2]
    params = jax.tree.map(lambda *ls: jnp.concatenate(ls),
                          *[o[0] for o in outs])
    return params, jnp.concatenate([o[2] for o in outs])


# ------------------------------------------------------------------ ARMA ---
@functools.partial(jax.jit, static_argnames=("steps",))
def _arima_fit_one(d, steps: int = 400, lr: float = 5e-2):
    """Fit ARMA(1,1) on the series d (T,) by conditional least squares:
    d_t = mu + phi d_{t-1} + theta eps_{t-1} + eps_t.  (Used on levels for
    the paper-faithful Eq. 3 model, or on first differences for the
    beyond-paper ARIMA(1,1,1) variant.)"""
    def css(theta_vec):
        mu, phi, th = theta_vec

        def step(eps_prev, pair):
            d_prev, d_t = pair
            pred = mu + phi * d_prev + th * eps_prev
            eps = d_t - pred
            return eps, eps

        _, eps = jax.lax.scan(step, 0.0, (d[:-1], d[1:]))
        return jnp.mean(eps ** 2)

    theta = jnp.zeros((3,))
    m = jnp.zeros((3,))
    v = jnp.zeros((3,))

    def opt_step(carry, i):
        theta, m, v = carry
        g = jax.grad(css)(theta)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (i + 1.0))
        vh = v / (1 - 0.999 ** (i + 1.0))
        theta = theta - lr * mh / (jnp.sqrt(vh) + 1e-8)
        theta = jnp.clip(theta, -0.98, 0.98)  # stationarity guard
        return (theta, m, v), None

    (theta, _, _), _ = jax.lax.scan(opt_step, (theta, m, v),
                                    jnp.arange(steps))
    # final eps state for forecasting
    def step(eps_prev, pair):
        d_prev, d_t = pair
        eps = d_t - (theta[0] + theta[1] * d_prev + theta[2] * eps_prev)
        return eps, None

    eps_T, _ = jax.lax.scan(step, 0.0, (d[:-1], d[1:]))
    return theta, eps_T, css(theta)


class ARMAForecaster(Forecaster):
    """Paper-faithful Eq. 3: ARMA(1,1) on metric LEVELS, per metric.

        y_t = mu + eps_t + theta_1 eps_{t-1} + phi_1 y_{t-1}

    Fit once on the pretraining distribution, this model exhibits exactly
    the 'significant shifts' under load-regime change the paper reports in
    §6.1 (the mean term is anchored to the training regime)."""

    differenced = False   # ARIMAD1Forecaster flips this (beyond-paper)

    def __init__(self, window: int = 1, steps: int = 400):
        self.window = window
        self.steps = steps
        self.scaler = Scaler()
        self.theta = None      # (M, 3)
        self.eps_T = None      # (M,)
        self._fitted = False

    def _series_for_fit(self, z):
        return np.diff(z, axis=0) if self.differenced else z

    def fit(self, series: np.ndarray, from_scratch: bool = False):
        if len(series) < 8:
            return self
        self.scaler.fit(series)
        z = self._series_for_fit(self.scaler.transform(series))
        thetas, epss = [], []
        for m in range(z.shape[1]):
            th, eT, _ = _arima_fit_one(jnp.asarray(z[:, m]), self.steps)
            thetas.append(np.asarray(th))
            epss.append(float(eT))
        self.theta = np.stack(thetas)
        self.eps_T = np.asarray(epss)
        self._fitted = True
        return self

    def predict(self, recent: np.ndarray):
        if not self._fitted:
            raise RuntimeError("model not fitted")
        z = self.scaler.transform(recent)
        mu, phi, th = self.theta[:, 0], self.theta[:, 1], self.theta[:, 2]
        if self.differenced:
            d_last = z[-1] - z[-2] if len(z) >= 2 else np.zeros_like(z[-1])
            y_next = z[-1] + mu + phi * d_last + th * self.eps_T
        else:
            y_next = mu + phi * z[-1] + th * self.eps_T
        return self.scaler.inverse(y_next), None

    def predict_batch(self, recents):
        """Closed-form one-step forecast vectorised over Z targets — pure
        numpy, no per-target loop."""
        if not self._fitted:
            raise RuntimeError("model not fitted")
        z = np.stack([self.scaler.transform(
            np.asarray(r, np.float64)[-2:]) for r in recents])   # (Z, <=2, M)
        mu, phi, th = self.theta[:, 0], self.theta[:, 1], self.theta[:, 2]
        if self.differenced:
            d_last = (z[:, -1] - z[:, -2] if z.shape[1] >= 2
                      else np.zeros_like(z[:, -1]))
            y_next = z[:, -1] + mu + phi * d_last + th * self.eps_T
        else:
            y_next = mu + phi * z[:, -1] + th * self.eps_T
        return self.scaler.inverse(y_next), None

    def valid(self):
        return self._fitted and np.isfinite(self.theta).all()

    def __getstate__(self): return dict(self.__dict__)
    def __setstate__(self, d): self.__dict__.update(d)


class ARIMAD1Forecaster(ARMAForecaster):
    """Beyond-paper: ARIMA(1,1,1) (first-differenced ARMA(1,1)).  On the
    Prometheus 1-minute-MA metric this persistence-anchored variant turns
    out to beat both paper models — recorded in EXPERIMENTS.md."""
    differenced = True


# -------------------------------------------------------------- ensemble ---
@functools.partial(jax.jit, static_argnames=("use_pallas", "arch"))
def _lstm_forward_members(stacked_params, xs, *, use_pallas: bool = False,
                          arch: str = "lstm"):
    """stacked_params: pytree with leading member axis E; xs (E, Z, W, M) ->
    (E, Z, M) — members vmapped, targets on ``lstm_forward``'s own batch
    axis, so E members x Z targets is one device dispatch (on the Pallas
    path each member's fused sequence kernel is batched by the vmap)."""
    def fwd(p, x):
        return lstm_forward(p, x, use_pallas=use_pallas, arch=arch)
    return jax.vmap(fwd)(stacked_params, xs)


_members_staged = Staged(_lstm_forward_members)


class EnsembleForecaster(Forecaster):
    """Deep ensemble of LSTMs — the Bayesian path of Algorithm 1: predictive
    std across members is the (un)certainty compared against the PPA's
    confidence threshold."""

    is_bayesian = True

    def __init__(self, n_members: int = 4, **kw):
        self.members = [LSTMForecaster(seed=i, **kw) for i in range(n_members)]
        self.window = self.members[0].window
        self._stack_cache: dict = {}

    def fit(self, series, from_scratch: bool = False):
        """All E members in ONE vmapped dispatch (their param pytrees ride
        ``lstm_fit_batch_stacked``'s batch axis, matching what
        ``predict_batch`` does for the forward); heterogeneous member
        architectures fall back to the member loop."""
        if lstm_fit_batch_stacked(self.members,
                                  [series] * len(self.members),
                                  from_scratch) is None:
            for m in self.members:
                m.fit(series, from_scratch=from_scratch)
        return self

    def predict(self, recent):
        preds = np.stack([m.predict(recent)[0] for m in self.members])
        return preds.mean(0), preds.std(0)

    def predict_batch(self, recents):
        """E members x Z targets in a SINGLE dispatch: member param pytrees
        stacked on one leading axis, each member's scaler-transformed
        (Z, W, M) window batch stacked alongside, ``lstm_forward`` vmapped
        over the member axis.  The stacked params are cached per member fit
        generation.  Falls back to one dispatch per member when members are
        non-stackable (heterogeneous architecture)."""
        ms = self.members
        m0 = ms[0]
        sig = lstm_stack_signature(m0)
        if not all(isinstance(m, LSTMForecaster) and m._fitted
                   and lstm_stack_signature(m) == sig for m in ms):
            preds = np.stack([m.predict_batch(recents)[0] for m in ms])
            return preds.mean(0), preds.std(0)
        if isinstance(recents, np.ndarray) and recents.ndim == 3:
            wins = np.asarray(recents, np.float64)[:, -m0.window:]
        else:
            wins = np.stack([np.asarray(r, np.float64)[-m0.window:]
                             for r in recents])
        z = np.stack([m.scaler.transform(wins) for m in ms])  # (E, Z, W, M)
        cache = getattr(self, "_stack_cache", None)
        if cache is None:
            cache = self._stack_cache = {}
        gens = tuple(m._fit_count for m in ms)
        if cache.get("gens") != gens:
            cache["gens"] = gens
            cache["stacked"] = stack_params(ms)
        preds = np.asarray(_members_staged(
            cache["stacked"], jnp.asarray(z), use_pallas=m0.use_pallas,
            arch=m0.arch))
        if m0.residual:
            preds = z[:, :, -1] + preds
        means = np.stack([m.scaler.inverse(p) for m, p in zip(ms, preds)])
        return means.mean(0), means.std(0)

    def valid(self):
        return all(m.valid() for m in self.members)

    def __getstate__(self):
        return {"members": [m.__getstate__() for m in self.members]}

    def __setstate__(self, d):
        # reconstruct members from scratch: __setstate__ runs on a bare
        # instance (pickle/deepcopy skip __init__), so self.members does
        # not exist yet
        self._stack_cache = {}
        members = []
        for s in d["members"]:
            m = LSTMForecaster.__new__(LSTMForecaster)
            m.__setstate__(s)
            members.append(m)
        self.members = members
        self.window = members[0].window if members else 1


def make_forecaster(kind: str, **kw) -> Forecaster:
    """The paper's ModelType argument (mirrors ``make_policy``):
    'lstm' | 'attn' (Attention-Double-LSTM) | 'arma' (paper Eq. 3) |
    'arima_d1' (beyond-paper) | 'ensemble'."""
    if kind == "lstm":
        return LSTMForecaster(**kw)
    if kind == "attn":
        return AttnLSTMForecaster(**kw)
    if kind in ("arma", "arima"):
        return ARMAForecaster(**kw)
    if kind == "arima_d1":
        return ARIMAD1Forecaster(**kw)
    if kind == "ensemble":
        return EnsembleForecaster(**kw)
    raise ValueError(f"unknown forecaster kind {kind!r}")
