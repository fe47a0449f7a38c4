"""The check runs one reference forward a block of targets over all sampled
ticks, the blocks on threads; the decisions' stabiliser keeps a running
maximum.  Each gives what the per-tick form
gives: the reference written tick by tick below (the models as they were
before the tick axis), the check written over it as one loop over ticks
and every target at once, and the stabiliser as a maximum over every
record in its window."""
import sys
import time

import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.harness import drive_cell, to_bf16
from chipbench.layout import Layout
from chipbench.models import attn, lstm


# -- the per-tick reference: (Z, W, M) windows -> (Z, M) --------------------
def matvec_tick(x, w):
    return (x[:, None, :] @ w)[:, 0]


def lstm_tick(xs, Wx, Wh, b, rnd):
    Z, W, _ = xs.shape
    H = Wh.shape[1]
    h = np.zeros((Z, H))
    c = np.zeros((Z, H))
    hs = []
    for t in range(W):
        gates = rnd(rnd(matvec_tick(xs[:, t], Wx)) + rnd(matvec_tick(h, Wh))
                    + b)
        i, f, g, o = np.split(gates, 4, axis=-1)
        c = rnd(rnd(lstm.sigmoid(f)) * c
                + rnd(lstm.sigmoid(i)) * rnd(np.tanh(g)))
        h = rnd(rnd(lstm.sigmoid(o)) * rnd(np.tanh(c)))
        hs.append(h)
    return np.stack(hs, axis=1)


def lstm_forward_tick(p, z, rnd):
    h = lstm_tick(z, p["Wx"], p["Wh"], p["b"], rnd)[:, -1]
    return rnd(rnd(matvec_tick(np.maximum(h, 0.0), p["Wo"])) + p["bo"])


def attn_forward_tick(p, z, rnd):
    hs = lstm_tick(z, p["Wx1"], p["Wh1"], p["b1"], rnd)
    H = hs.shape[-1]
    q = rnd(matvec_tick(hs[:, -1], p["Wa"]))
    scores = rnd(rnd(np.einsum("zwh,zh->zw", hs, q)) * H ** -0.5)
    e = np.exp(scores - scores.max(axis=-1, keepdims=True))
    alpha = rnd(e / e.sum(axis=-1, keepdims=True))
    ctx = rnd(alpha[:, :, None] * hs)
    h2 = lstm_tick(ctx, p["Wx2"], p["Wh2"], p["b2"], rnd)[:, -1]
    return rnd(rnd(matvec_tick(np.maximum(h2, 0.0), p["Wo"])) + p["bo"])


FORWARD_TICK = {lstm.PROGRAM_CLASS: lstm_forward_tick,
                attn.PROGRAM_CLASS: attn_forward_tick}


def forecast_tick(arch, leaves, mean, std, win, residual,
                  rnd=reference.identity):
    z = rnd(np.clip((win - mean[:, None, :]) / std[:, None, :],
                    -reference.Z_CLIP, reference.Z_CLIP))
    forward = FORWARD_TICK[arch.PROGRAM_CLASS]
    net = forward({k: rnd(v) for k, v in leaves.items()}, z, rnd)
    if residual:
        net = rnd(z[:, -1, :] + net)
    return net * std + mean


def check_tick(run, control=False):
    """The check as one loop over ticks, every target at once."""
    cfg, a = run.config, run.config["assumed"]
    mean, std = reference.scaler_stats(run.history)
    k = int(cfg["key_metric_idx"])
    decide = reference.Decider(a["threshold"], a["min_replicas"],
                               cfg["tolerance"], cfg["stabilization_s"])
    cur = np.full(run.Z, a["min_replicas"], np.int64)
    mismatches = 0
    for j, dec in enumerate(run.decisions):
        want = decide(run.t_at(j), run.row_at(j)[:, k], run.key_fc[j], cur,
                      int(a["max_replicas"]))
        mismatches += int((want != dec).sum())
        cur = dec
    W = int(cfg["window"])
    leaves = {n: v.astype(np.float64) for n, v in run.leaves.items()}
    gap, missing = 0.0, 0
    for j in run.check_ticks:
        win = np.stack([run.row_at(i) for i in range(j - W + 1, j + 1)],
                       axis=1)
        want = forecast_tick(run.arch, leaves, mean, std, win,
                             cfg["residual"])
        got = forecast_tick(run.arch, leaves, mean, std, win,
                            cfg["residual"], rnd=to_bf16) if control \
            else run.sampled[j]
        diff = np.abs(got - want) / std
        seen = np.isfinite(diff).all(axis=1)
        missing += int((~seen).sum())
        if seen.any():
            gap = max(gap, float(np.max(diff[seen])))
    return {"forecast_gap_z": gap, "missing_forecasts": missing,
            "decision_mismatches": mismatches}


# -- the tick-batched reference against the per-tick loop -------------------
@pytest.mark.parametrize("rnd", [reference.identity, to_bf16],
                         ids=["float64", "bf16"])
@pytest.mark.parametrize("arch,W", [(lstm, 1), (lstm, 3), (attn, 8)],
                         ids=["lstm-w1", "lstm-w3", "attn-w8"])
def test_tick_batched_forecast_equals_the_per_tick_loop(arch, W, rnd):
    Z, T, H, M = 24, 5, 50, 5
    rng = np.random.default_rng([7, W])
    leaves = {n: rng.standard_normal((Z,) + s).astype(np.float32)
              .astype(np.float64) * H ** -0.5
              for n, s in arch.leaf_shapes(H, M).items()}
    mean = rng.uniform(100.0, 4000.0, (Z, M))
    std = mean * rng.uniform(0.05, 0.3, (Z, M))
    win = mean[:, None, None, :] * np.exp(
        0.2 * rng.standard_normal((Z, T, W, M)))
    got = reference.forecast(arch, leaves, mean, std, win, True, rnd=rnd)
    assert got.shape == (Z, T, M)
    want = np.stack([forecast_tick(arch, leaves, mean, std, win[:, t], True,
                                   rnd=rnd) for t in range(T)], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)


# -- the whole check against the per-tick check -----------------------------
def spoil(run):
    """Some wrong answers, so that every number compared is non-zero: a
    changed decision, a forecast missing at one tick, one off by 1%."""
    ticks = run.check_ticks
    run.decisions[len(run.decisions) // 2][run.Z - 1] += 1
    run.sampled[ticks[0]][2, 1] = np.nan
    run.sampled[ticks[-1]][5, 0] *= 1.01


@pytest.mark.parametrize("cell", ["lstm-tiny", "attn-tiny"])
def test_check_equals_the_per_tick_check(tiny_root, monkeypatch, cell):
    # blocks smaller than the tiny cell's 8 targets, of uneven sizes
    monkeypatch.setattr(harness, "CHECK_BLOCK", 3)
    layout = Layout(tiny_root, tiny_root / "chipbench")
    run = drive_cell(layout, cell, 2**31 + 11, 0.3, False,
                     time.perf_counter(), require_tpu=False, log=sys.stdout)
    assert len(run.check_ticks) >= 2
    for spoilt in (False, True):
        if spoilt:
            spoil(run)
        for control in (False, True):
            got = {k: c["value"] for k, c in
                   run.check(control=control).items()}
            want = check_tick(run, control=control)
            assert got["missing_forecasts"] == want["missing_forecasts"]
            assert got["decision_mismatches"] == \
                want["decision_mismatches"]
            assert got["forecast_gap_z"] == pytest.approx(
                want["forecast_gap_z"], rel=1e-12, abs=0.0)
            if spoilt and not control:
                assert got["missing_forecasts"] == 1
                assert got["decision_mismatches"] >= 1
                assert got["forecast_gap_z"] > 1e-3
    assert run.check_threads >= 1
    assert set(run.check_s) == {"decisions", "forecasts"}


# -- the running maximum against the maximum over every record --------------
class DeciderStacked(reference.Decider):
    """The stabiliser as a maximum over every record in the window."""

    def __call__(self, t, current_key, forecast_key, cur, max_r):
        key = np.where(np.isfinite(forecast_key), forecast_key, current_key)
        n = reference.threshold_policy(key, cur, self.threshold,
                                       self.min_replicas, self.tolerance)
        n = np.minimum(n, max_r)
        self.recs = getattr(self, "recs", []) + [(t, n)]
        self.recs = [(tt, d) for tt, d in self.recs
                     if tt >= t - self.window_s]
        recmax = np.max([d for _, d in self.recs], axis=0)
        return np.where(n < cur, np.minimum(recmax, max_r), n)


@pytest.mark.parametrize("steps", ["even", "uneven"])
def test_running_maximum_decides_as_the_maximum_over_the_window(steps):
    Z, n, max_r = 64, 400, 64
    rng = np.random.default_rng([11, len(steps)])
    # even: one record leaves as one comes; uneven: gaps of up to 400 s
    # empty the window at once, bursts fill it with many records
    dt = np.full(n, 15.0) if steps == "even" else rng.choice(
        [0.5, 3.0, 15.0, 90.0, 400.0], n)
    times = 3600.0 + np.cumsum(dt)
    level = rng.uniform(0.0, 20000.0, Z) * np.exp(np.cumsum(
        rng.normal(0.0, 0.3, (n, Z)), axis=0))
    fc = level * rng.uniform(0.8, 1.2, (n, Z))
    fc[rng.random((n, Z)) < 0.05] = np.nan
    got = reference.Decider(500.0, 1, 0.1, 300.0)
    want = DeciderStacked(500.0, 1, 0.1, 300.0)
    cur = np.ones(Z, np.int64)
    for j in range(n):
        a = got(times[j], level[j], fc[j], cur, max_r)
        b = want(times[j], level[j], fc[j], cur, max_r)
        np.testing.assert_array_equal(a, b)
        # the program's decisions as the next current count: the
        # reference's own, and now and then others
        cur = np.where(rng.random(Z) < 0.1, rng.integers(1, max_r, Z), a)
