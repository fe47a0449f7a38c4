"""Device-mesh execution engine for the sharded control plane (DESIGN.md §9).

``ShardedControlPlane`` keeps its tick state in host numpy: a (Zs, R, M)
metric ring per shard, f64 scaler transforms, and a ``predict_from_stack``
that re-uploads the window batch (and gathers stacked weights) every tick.
Once dispatch is fused that host round-trip IS the tick wall at Z >= 10^4.
This module moves the forecast half of the tick onto a JAX device mesh:

* **mesh** — one physical axis ``('shards',)`` over D local devices
  (``distributed.sharding.control_mesh``); the plane's Z-target axis is
  partitioned over it with ``NamedSharding``/``PartitionSpec``.
* **device-resident state** — the metric ring (Zp, R, M) f32, the
  forecast's weight operands, and the stacked scaler stats live on the
  mesh BETWEEN ticks.  Per tick the host uploads one (Zp, M) row batch and
  downloads one (Zp, M) prediction batch; the ring shifts in place on
  device (``jnp`` functional update — the old buffer stays valid, which
  is exactly the double-buffer snapshot the async tick needs for free).
* **two dispatch policies** — ``coalesce_dispatch=True`` gangs the whole
  plane into ONE jitted program and lets GSPMD partition it over the mesh;
  ``False`` routes the per-shard path through ``jax.shard_map`` so each
  device runs its own block program (the multi-device deployment shape).
* **install form** — ``refresh`` installs the weights in the form the
  forecast program reads (``forecaster.stacked_operands``): for the fused
  LSTM kernel at window 1 one f32 row per target (``kernels/lstm_seq.py``
  ``stacked_form``) of 128-lane-aligned blocks, whose default TPU layout
  is the one the Mosaic call reads (stacked 3-D leaves default to a
  target-minor layout, which XLA would relay out on every tick), without
  ``Wh`` (h0 = 0 makes ``h @ Wh`` zero).  A tick then moves no weight
  bytes but the kernel's own reads.  Past window 1, and for the attention
  kernel, the leaves are installed as they are.
* **invalidate-on-refit-commit** — the installed weights and scalers are
  rebuilt and re-uploaded only when the plane's refit epoch moves (the
  same epoch the fused host cache keys on), never per tick.

Bitwise device-count invariance: every per-target computation here is
row-independent (batched GEMV per target, no cross-target reductions), so
partitioning the Z axis over 1, 2 or 8 devices cannot change any row's
numerics — ``tests/test_device_plane.py`` asserts tick results are
bitwise identical across D.  Against the host plane the engine computes
in f32 end-to-end (the host path standardises in f64), so equivalence is
decision-level + allclose, like the Pallas kernel path.

The reactive guardrail stage (DESIGN.md §10, docs/guardrail.md) composes
with this engine for free: guard state (``_grd_prev`` armed forecasts,
consecutive-overshoot counters) lives in per-shard host arrays inside
``_VecShard`` and the plane's device-mode ``finish_tick`` feeds each
shard's ``decide`` through the same zero-copy shard views (``_shard_cuts``)
as the unguarded plane — the guard reads the realised key metric from the
host-tracked last-row buffer and never touches the device ring, so the
D-invariance and tick-transfer budget above are unchanged (bitwise
invariance with the guard armed is asserted in tests/test_guardrail.py).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import obs
from repro.core.faults import FaultLog, Staged
from repro.core.forecaster import (Z_CLIP, lstm_stack_signature,
                                   stack_scaler_stats, stacked_forward,
                                   stacked_operands)
from repro.core.metrics import N_METRICS
from repro.distributed.sharding import CONTROL_AXIS, control_mesh

FORCE_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices_env(n: int = 8, env: dict | None = None) -> dict:
    """Environment for a subprocess that should see ``n`` virtual CPU
    devices — the forced-host-device trick CI uses to exercise the mesh
    plane without accelerators.  Must be set before jax initialises, hence
    the subprocess (tests/conftest.py re-execs through this)."""
    out = dict(os.environ if env is None else env)
    flags = [f for f in out.get("XLA_FLAGS", "").split()
             if not f.startswith(FORCE_HOST_DEVICES_FLAG)]
    flags.append(f"{FORCE_HOST_DEVICES_FLAG}={int(n)}")
    out["XLA_FLAGS"] = " ".join(flags)
    out.setdefault("JAX_PLATFORMS", "cpu")
    return out


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


class DevicePlaneEngine:
    """Device-resident forecast state + dispatch for one control plane.

    The plane (core/control_plane.py) keeps owning collect / evaluate /
    actuate on host numpy; this engine owns exactly the state that used to
    cross the host-device boundary every tick: the metric ring, the
    per-target weights (installed in the forecast kernel's form) and the
    stacked scaler stats.

    The engine computes predictions for ALL rows and the plane masks
    non-candidates with NaN on host — a host-side candidate gather would
    reintroduce the per-tick device round-trip, and an all-rows program
    keeps shapes static across ticks (one compile).
    """

    def __init__(self, Z: int, window: int, residual: bool,
                 use_pallas: bool, *, device_mesh=None,
                 coalesce_dispatch: bool = True, ring_rows: int | None = None,
                 arch: str = "lstm", faults: FaultLog | None = None):
        self.mesh = (device_mesh if device_mesh is not None
                     and not isinstance(device_mesh, int)
                     else control_mesh(device_mesh))
        if tuple(self.mesh.axis_names) != (CONTROL_AXIS,):
            raise ValueError("device plane needs a 1-D ('shards',) mesh "
                             f"(got axes {self.mesh.axis_names})")
        self.n_devices = int(self.mesh.devices.size)
        self.Z = int(Z)
        self.Zp = _pad_to(max(self.Z, self.n_devices), self.n_devices)
        self.window = int(window)
        self.residual = bool(residual)
        self.use_pallas = bool(use_pallas)
        self.arch = str(arch)
        self.R = int(ring_rows if ring_rows is not None
                     else max(self.window + 1, 8))
        self.coalesce = bool(coalesce_dispatch)
        self.faults = faults if faults is not None else FaultLog()
        self._s_rows = NamedSharding(self.mesh, P(CONTROL_AXIS, None))
        self._s_ring = NamedSharding(self.mesh, P(CONTROL_AXIS, None, None))
        self.ring = jax.device_put(
            np.zeros((self.Zp, self.R, N_METRICS), np.float32), self._s_ring)
        # reused host staging buffer for the per-tick row upload (pad rows
        # beyond Z are never candidates, so zeros are fine)
        self._row_buf = np.zeros((self.Zp, N_METRICS), np.float32)
        self.epoch: int | None = None     # refit epoch of the device caches
        self._stacked = None              # installed operands, leading Zp
        self._mean = self._std = None     # device (Zp, M) f32
        self._valid = np.zeros(self.Z, bool)
        self._push = Staged(jax.jit(ppa_ring_push))
        self._push_row = jax.jit(self._push_row_fn)
        self._fwd = Staged(forecast_program(
            self.mesh, self.window, self.residual, self.use_pallas,
            self.arch, self.coalesce))
        # work counters behind ShardedControlPlane.tick_stats()
        self.h2d_bytes = 0                # per-tick row uploads
        self.d2h_bytes = 0                # per-tick forecast downloads
        self.weight_installs = 0          # refreshes that re-uploaded
        self.install_bytes = 0
        self._out_bytes = self._row_buf.nbytes   # the (Zp, M) forecast

    def programs(self) -> tuple[Staged, ...]:
        """The engine's per-tick device programs (builds are counted by
        each ``Staged``)."""
        return self._fwd, self._push

    # ----------------------------------------------------- ring updates --
    @staticmethod
    def _push_row_fn(ring, i, row):
        shifted = jnp.concatenate([ring[i, 1:], row[None, :]], axis=0)
        return ring.at[i].set(shifted)

    def push_rows(self, rows: np.ndarray):
        """One whole-plane ring shift on device: uploads a single (Zp, M)
        f32 row batch (the tick's only host->device transfer)."""
        nbytes = self._row_buf.nbytes
        with obs.span("ppa.collect.upload", bytes=nbytes):
            self._row_buf[:self.Z] = rows
            self.h2d_bytes += nbytes
            if self.R == 1:
                # window-1 ring: the shift is the identity, so the upload
                # IS the new ring — no shift dispatch (device_put builds a
                # fresh buffer, so earlier snapshots stay valid)
                self.ring = jax.device_put(
                    self._row_buf[:, None, :], self._s_ring)
                return
            dev_rows = jax.device_put(self._row_buf, self._s_rows)
            self.ring = self._push(self.ring, dev_rows)

    def push_row(self, i: int, row: np.ndarray):
        """Single-target observe (the scalar ``observe`` API)."""
        self.ring = self._push_row(self.ring, jnp.int32(i),
                                   jnp.asarray(row, jnp.float32))

    def snapshot(self):
        """The formulated window state — an immutable device array ref;
        later pushes build new buffers and never mutate it."""
        return self.ring

    # ------------------------------------------------------ weight cache --
    def refresh(self, models, epoch: int):
        """Install the forecast's weight operands and scaler stats iff the
        plane's refit epoch moved (invalidate-on-refit-commit).  The
        operands are built on the host in the form the forecast program
        reads (``stacked_operands``): for the fused LSTM kernel at window 1
        one row per target of 128-lane-aligned blocks with no ``Wh``, so
        a tick moves no weight bytes but the kernel's own reads.  Runs on
        the control thread between ticks, so no in-flight forecast can
        read a half-installed stack."""
        if self.epoch == epoch:
            return
        self._valid = np.array(
            [self._model_ok(m) for m in models], bool)

        def stack(leaf):
            arrs = [np.asarray(m.params[leaf], np.float32) for m in models]
            buf = np.zeros((self.Zp,) + arrs[0].shape, np.float32)
            buf[:self.Z] = np.stack(arrs)
            return buf

        operands = stacked_operands(stack, self.window,
                                    use_pallas=self.use_pallas,
                                    arch=self.arch, xp=np)
        mean, std = stack_scaler_stats(models)
        mean_p = np.zeros((self.Zp, N_METRICS), np.float32)
        std_p = np.ones((self.Zp, N_METRICS), np.float32)
        mean_p[:self.Z] = mean
        std_p[:self.Z] = std
        self._stacked = jax.tree.map(
            lambda leaf: jax.device_put(leaf, self._s_leaf(leaf)), operands)
        self._mean = jax.device_put(mean_p, self._s_rows)
        self._std = jax.device_put(std_p, self._s_rows)
        self.epoch = epoch
        self.weight_installs += 1
        self.install_bytes += (sum(a.nbytes for a in jax.tree.leaves(operands))
                               + mean_p.nbytes + std_p.nbytes)

    def _s_leaf(self, leaf: np.ndarray) -> NamedSharding:
        return NamedSharding(
            self.mesh, P(CONTROL_AXIS, *(None,) * (leaf.ndim - 1)))

    @staticmethod
    def _model_ok(m) -> bool:
        try:
            return bool(m.valid())
        except Exception:
            return False

    # --------------------------------------------------------- dispatch --
    def forecast(self, ring_ref, counts: np.ndarray, stale=None):
        """Forecast every target from a ring snapshot: returns
        ``(means (Z, M) f32 with NaN rows for non-candidates, cand (Z,))``.
        Reads only device caches + the immutable snapshot — safe on a
        worker thread while the driver keeps pushing next-window rows.
        ``stale`` (optional (Z,) bool, DESIGN.md §13) masks TTL-expired
        targets out of the candidate set host-side, so their NaN means
        route them down the reactive path — and a full-plane blackout
        skips the device dispatch entirely."""
        cand = self._valid & (counts >= self.window + 1)
        if stale is not None:
            cand = cand & ~stale
        if not cand.any():
            return np.full((self.Z, N_METRICS), np.nan, np.float32), cand
        try:
            with obs.span("ppa.forecast.dispatch"):
                out = self._fwd(self._stacked, self._mean, self._std,
                                ring_ref)
            # one np.asarray waits for the program and copies its output
            with obs.span("ppa.forecast.readback", bytes=self._out_bytes):
                host = np.asarray(out)
                if cand.all():
                    # steady state: every row is a candidate, skip the mask
                    means = host[:self.Z]
                else:
                    means = np.full((self.Z, N_METRICS), np.nan, np.float32)
                    means[cand] = host[:self.Z][cand]
        except Exception as e:
            # robust: a failed gang dispatch -> every target reactive,
            # counted; a program that fails to build raises ProgramFault
            self.faults.forecast_failed(e)
            return np.full((self.Z, N_METRICS), np.nan, np.float32), \
                np.zeros(self.Z, bool)
        self.d2h_bytes += host.nbytes
        return means, cand


def ppa_ring_push(ring, rows):
    """The engine's ring shift (module ``jit_ppa_ring_push``): drop the
    oldest row of every target, append ``rows``.  Functional: the returned
    buffer replaces the ring, and a snapshot taken before the push stays
    valid (the async tick's double buffer, no copy needed)."""
    return jnp.concatenate([ring[:, 1:], rows[:, None, :]], axis=1)


def forecast_program(mesh, window: int, residual: bool, use_pallas: bool,
                     arch: str, coalesce: bool):
    """The engine's jitted forecast program: ``(stacked, mean, std, ring)``
    with a leading padded target axis on each, ``stacked`` being the
    installed weight operands (``stacked_operands``) -> ``(Zp, M)``
    forecasts in metric units (standardise, stacked forward, residual,
    inverse).  Its module is ``jit_ppa_forecast`` in a device trace."""
    W = window
    rows = (P(CONTROL_AXIS), P(CONTROL_AXIS))

    def net_fn(stacked, z):
        return stacked_forward(stacked, z, use_pallas=use_pallas, arch=arch)

    if coalesce and use_pallas:
        # GSPMD cannot partition a Mosaic kernel: the gang program runs the
        # kernel per device under shard_map and partitions the rest itself
        net_fn = jax.shard_map(net_fn, mesh=mesh, in_specs=rows,
                               out_specs=P(CONTROL_AXIS), check_vma=False)

    def ppa_forecast(stacked, mean, std, ring):
        win = ring[:, -W:, :]
        z = jnp.clip((win - mean[:, None, :]) / std[:, None, :],
                     -Z_CLIP, Z_CLIP)
        net = net_fn(stacked, z)
        if residual:
            net = z[:, -1, :] + net
        return net * std + mean

    if coalesce:
        # gang dispatch: ONE program, GSPMD partitions the Z axis over the
        # mesh following the argument shardings
        return jax.jit(ppa_forecast)
    # per-shard dispatch: shard_map runs the block program per device
    # (PartitionSpecs shorter than an array's rank replicate the trailing
    # dims; the stacked-params dict takes P('shards') as a pytree prefix).
    # Every row is computed on its own device and nothing is replicated,
    # so there is no varying-axes typing to check — and the Pallas
    # kernels' out_shapes carry none, which check_vma would refuse.
    return jax.jit(jax.shard_map(
        ppa_forecast, mesh=mesh, in_specs=rows + rows,
        out_specs=P(CONTROL_AXIS), check_vma=False))


def engine_for_plane(plane, device_mesh, coalesce_dispatch: bool,
                     faults: FaultLog | None = None
                     ) -> tuple[DevicePlaneEngine, list]:
    """Validate a ``ShardedControlPlane``'s target set for the device path
    and build its engine + plane-order model list.  The device plane only
    takes the homogeneous per-target stacked-LSTM shape — exactly the set
    the fused gang path accepts."""
    if not plane.per_target_models:
        raise ValueError("device_mesh needs per-target models (a shared "
                         "model owns its own predict_batch dispatch)")
    if not all(s.vectorized for s in plane.shards):
        raise ValueError("device_mesh needs every shard on the columnar "
                         "path (vectorisable policies + stackable LSTMs)")
    # plane-order model list without an O(Z^2) per-name lookup
    models = [None] * len(plane.target_names)
    for shard, idx in plane._shard_rows:
        tm = shard.target_models()
        for j, gi in enumerate(idx):
            models[gi] = tm[j]
    sig = lstm_stack_signature(models[0])
    if not all(lstm_stack_signature(m) == sig for m in models):
        raise ValueError("device_mesh needs homogeneous stackable models "
                         "across shards")
    m0 = models[0]
    use_pallas = (m0.use_pallas if plane.use_pallas is None
                  else plane.use_pallas)
    # ring sized to exactly the forward window: the plane tracks counts
    # and last rows on host, so deeper device history is dead weight the
    # per-tick push shift would pay for (8x at window=1 vs the default)
    engine = DevicePlaneEngine(
        len(models), m0.window, m0.residual, use_pallas,
        device_mesh=device_mesh, coalesce_dispatch=coalesce_dispatch,
        ring_rows=m0.window, arch=m0.arch, faults=faults)
    return engine, models
