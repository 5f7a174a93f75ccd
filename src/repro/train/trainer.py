"""ES-RNN trainer: joint per-series + shared-weight optimization loop.

Production posture:
* fused supersteps (``scan_steps > 1``): K steps compile into one donated
  ``lax.scan`` dispatch over a precomputed on-device batch schedule
  (``repro.train.engine``); the host syncs once per superstep, which is
  where eval, checkpointing, the straggler EWMA, and hooks run,
* per-step engine (``scan_steps == 1``): the host reads each step's loss
  ``IN_FLIGHT`` steps late, so its index, dispatch and bookkeeping overlap
  the device's queued steps; it drains every pending loss before eval,
  checkpoint or preemption work, and on every step when an ``on_step`` hook
  is present, so those see the params of the step they name,
* checkpoint/restart (atomic, resumable mid-epoch because the batch schedule
  is stateless in ``step`` -- a resume lands on any superstep boundary and
  re-aligns with the same absolute eval/ckpt steps),
* SIGTERM/SIGINT preemption hook -> checkpoint-and-exit (how a 1000-node job
  survives maintenance evictions); the per-step engine honors the request
  after the step being dispatched, fused supersteps at the next superstep
  boundary,
* straggler watchdog: wall-time EWMA per step (per-step normalized within a
  superstep; in the per-step engine the interval between consecutive loss
  reads); steps slower than ``straggler_factor``x the EWMA are logged
  (on real fleets this feeds the scheduler; here it exercises the code path),
* validation-driven best-checkpoint tracking (sMAPE on the held-out window,
  paper section 5.1),
* program spans (``repro.analysis.spans``): per (super)step a ``fit.step``
  holding ``fit.index`` (the batch schedule to the device), ``fit.dispatch``
  and ``fit.loss_sync`` (the host's wait for losses: in the per-step engine
  one per step read, with id ``lag``, the number of later steps already
  dispatched when it waited), then a ``fit.boundary`` holding ``fit.eval``.
  A step asks once whether a trace is recording.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import signal
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import spans
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import losses as L
from repro.core.esrnn import ESRNNConfig, esrnn_forecast, esrnn_init
from repro.core.heads import frozen_param_groups
from repro.data.pipeline import (
    PreparedData, batch_indices, batch_schedule, chunk_batch_schedule,
    chunk_layout, chunk_visit_plan,
)
from repro.train.engine import (
    make_chunk_step_fn, make_chunk_superstep_fn, make_perstep_fn,
    make_step_fn, make_superstep_fn, next_boundary, segment_steps,
    split_frozen,
)
from repro.train.host_table import HostStateTable
from repro.train.optimizer import AdamConfig, adam_init, adam_init_sparse

log = logging.getLogger("repro.train")

# steps the per-step engine keeps dispatched past the one whose loss it
# reads: one step of lead covers the host's index, dispatch and the loss's
# D2H notification; the second absorbs host jitter (frames, GC)
IN_FLIGHT = 2


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 256
    n_steps: int = 300
    lr: float = 1e-3
    per_series_lr_mult: float = 10.0    # HW params learn faster (Smyl setup)
    clip_norm: Optional[float] = 20.0
    seed: int = 0
    eval_every: int = 50
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep: int = 3
    straggler_factor: float = 3.0
    data_parallel: int = 0              # devices for the series-sharded path
                                        # (0/1 = single-device)
    scan_steps: int = 1                 # steps fused per donated superstep
                                        # (1 = per-step dispatch loop)
    sparse_adam: bool = False           # segment per-series Adam: update only
                                        # the batch's HW rows (lazy moments)
    compress_grads: bool = False        # error-feedback int8 compression of
                                        # the shared-weight gradient exchange
                                        # (per-series rows stay exact; dense
                                        # Adam only)
    series_chunk: int = 0               # > 0: partition the N series into
                                        # device-sized row chunks; the HW
                                        # table + its sparse-Adam state live
                                        # in a host-resident HostStateTable
                                        # and stream through the device one
                                        # chunk at a time (0 = resident)
    chunk_resident: bool = False        # debug reference: run the chunk-major
                                        # schedule with the full table kept on
                                        # device -- the trajectory the
                                        # streaming path must reproduce
                                        # (TrainConfig-only; not spec-exposed)

    @classmethod
    def from_spec(cls, spec, *, ckpt_dir: Optional[str] = None,
                  n_steps: Optional[int] = None) -> "TrainConfig":
        """Build from a ``repro.forecast.ForecastSpec``.

        The spec carries the two learning rates as first-class fields
        (``rnn_lr`` for shared weights, ``hw_lr`` for the per-series HW
        group); the trainer's group machinery consumes them as a ratio.
        """
        return cls(
            batch_size=spec.batch_size,
            n_steps=spec.n_steps if n_steps is None else n_steps,
            lr=spec.rnn_lr,
            per_series_lr_mult=spec.hw_lr / spec.rnn_lr,
            clip_norm=spec.clip_norm,
            seed=spec.seed,
            eval_every=spec.eval_every,
            ckpt_every=spec.ckpt_every,
            ckpt_dir=ckpt_dir,
            keep=spec.keep,
            data_parallel=spec.data_parallel,
            scan_steps=spec.scan_steps,
            sparse_adam=spec.sparse_adam,
            compress_grads=getattr(spec, "compress_grads", False),
            series_chunk=getattr(spec, "series_chunk", 0),
        )


class PreemptionHandler:
    """Converts SIGTERM/SIGINT into a cooperative checkpoint-and-exit flag."""

    def __init__(self):
        self.requested = False
        self._prev = {}

    def install(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._prev[sig] = signal.signal(sig, self._handle)

    def _handle(self, signum, frame):
        self.requested = True

    def uninstall(self):
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)


def train_esrnn(
    model: ESRNNConfig,
    data: PreparedData,
    cfg: TrainConfig,
    *,
    params=None,
    hooks: Optional[Dict[str, Callable]] = None,
    mesh=None,
) -> Dict:
    """Train; returns dict(params, history, resumed_from).

    ``model`` is an :class:`~repro.core.esrnn.ESRNNConfig`; training runs
    through the pure functional API.

    ``mesh``: optional 1-D series mesh (``repro.sharding.series``). With more
    than one device the loss runs series-sharded under ``shard_map``: each
    device owns its slice of the batch and of the gathered per-series HW
    rows (device-local gradients), while the shared RNN/head weights stay
    replicated with all-reduced gradients. The batch schedule, optimizer,
    and checkpoint format are identical to the single-device path, so the
    loss trajectory matches up to float summation order. If ``mesh`` is None
    a ``cfg.data_parallel > 1`` builds one over the first that many local
    devices.

    ``cfg.scan_steps > 1`` switches to the fused superstep engine
    (``repro.train.engine``): K steps per donated ``lax.scan`` dispatch over
    a precomputed on-device batch schedule, host sync + eval/ckpt/hooks at
    superstep boundaries only. The per-step loss trajectory is the same math
    in the same order, so histories match the per-step engine; the
    ``on_step`` hook fires once per superstep with the segment's loss
    *array* instead of once per step with a float. Composes with ``mesh``
    (the scan wraps the ``shard_map``-ped loss) and ``use_pallas``.

    ``cfg.sparse_adam`` switches the per-series Holt-Winters table to the
    sparse segment update (``adam_update_sparse``): only the batch's rows
    are touched each step, skipped rows catch up their Adam moments in
    closed form. Off by default -- untouched rows no longer drift along
    stale momentum, which changes trajectories slightly vs dense Adam.
    """
    mcfg = model
    if cfg.series_chunk and cfg.series_chunk > 0:
        if cfg.compress_grads:
            raise ValueError(
                "series_chunk > 0 requires the sparse optimizer path and "
                "compress_grads requires the dense one: the chunked fit "
                "never materializes a shared-gradient exchange to compress")
        if not cfg.sparse_adam:
            log.info("series_chunk=%d: enabling sparse per-series Adam "
                     "(the chunked path only ever holds the batch's rows)",
                     cfg.series_chunk)
            cfg = dataclasses.replace(cfg, sparse_adam=True)
        if not cfg.chunk_resident:
            return _train_chunked(mcfg, data, cfg, params=params,
                                  hooks=hooks, mesh=mesh)
    mesh = _resolve_train_mesh(cfg, mesh)
    if mesh is not None:
        from repro.sharding.series import check_series_divisible

        if cfg.series_chunk and cfg.series_chunk > 0:
            per_chunk, _ = chunk_layout(
                data.n_series, cfg.series_chunk, cfg.batch_size)
            for _, _, bs_c, _ in per_chunk:
                check_series_divisible(bs_c, mesh)
        else:
            check_series_divisible(min(cfg.batch_size, data.n_series), mesh)
        log.info("series-data-parallel training on %d devices (%s)",
                 mesh.devices.size, ",".join(mesh.axis_names))
    if mcfg.use_pallas:
        # trains end-to-end: hw_scan/lstm_cell carry custom_vjp backward
        # kernels (interpret mode off-TPU), so no forward-only fallback here
        log.info("training through the Pallas kernel path (backend=%s)",
                 jax.default_backend())
    cfg_adam = AdamConfig(
        lr=cfg.lr,
        clip_norm=cfg.clip_norm,
        group_lr={"per_series": cfg.per_series_lr_mult, "default": 1.0},
    )
    n = data.n_series
    if params is None:
        params = esrnn_init(jax.random.PRNGKey(cfg.seed), mcfg, n)
    else:
        # the engines donate (params, opt_state) unless hooks are present;
        # copy the caller's tree once so their reference stays valid
        params = jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), params)
    # optimizer state covers the trainable subtree only: the head registry
    # declares groups it keeps fixed (e.g. the esn reservoir), and those
    # carry no gradients, no Adam moments, and no checkpointed moment state
    frozen = frozen_param_groups(mcfg)
    trainable, _ = split_frozen(params, frozen)
    if frozen:
        log.info("head %r freezes param group(s) %s: training %s + hw only",
                 mcfg.head, sorted(frozen),
                 sorted(k for k in trainable if k != "hw"))
    opt_state = (adam_init_sparse(trainable) if cfg.sparse_adam
                 else adam_init(trainable))
    if cfg.compress_grads:
        if cfg.sparse_adam:
            raise ValueError(
                "compress_grads requires dense Adam (sparse_adam=False): "
                "the sparse path has no shared-gradient exchange to compress")
        from repro.train.grad_compression import init_error_state

        # step state grows an error-feedback residual over the shared
        # trainable groups; checkpoints carry it like any other opt leaf
        opt_state = (opt_state, init_error_state(
            {k: v for k, v in trainable.items() if k != "hw"}))
        log.info("error-feedback int8 compression of shared grads enabled")
    start_step = 0

    ckpt = Checkpointer(cfg.ckpt_dir, keep=cfg.keep) if cfg.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        try:
            start_step, (params, opt_state) = ckpt.restore((params, opt_state))
        except ValueError as e:
            # checkpoints are engine-portable (scan_steps), but the sparse
            # optimizer state carries an extra per-row clock: flipping
            # sparse_adam across a resume is a real state mismatch. Other
            # restore failures (shape drift etc.) pass through untouched.
            if "tree structure mismatch" not in str(e):
                raise
            raise ValueError(
                f"cannot resume from {cfg.ckpt_dir}: {e}. If this run was "
                f"checkpointed with a different sparse_adam setting "
                f"(currently {cfg.sparse_adam}), resume with the original "
                "setting -- the dense and sparse Adam states are not "
                "interchangeable") from e
        log.info("resumed from step %d", start_step)

    y_all = jnp.asarray(data.train)
    cats_all = jnp.asarray(data.cats)
    mask_all = jnp.asarray(data.mask)
    bs = min(cfg.batch_size, n)

    # The pure step -- shared verbatim by the per-step loop and the fused
    # scan, so the two engines walk float-identical trajectories. The
    # observation mask keeps left-padded (variable-length) positions out of
    # the loss; it is all-ones for equalized data.
    step_fn = make_step_fn(mcfg, cfg_adam, y_all, cats_all, mask_all,
                           mesh=mesh, sparse=cfg.sparse_adam, frozen=frozen,
                           compress=cfg.compress_grads)

    @jax.jit
    def val_smape(params):
        fc = esrnn_forecast(mcfg, params, y_all, cats_all)
        h = min(fc.shape[1], data.val_target.shape[1])
        return L.smape(fc[:, :h], jnp.asarray(data.val_target)[:, :h])

    pre = PreemptionHandler()
    pre.install()
    history = {"loss": [], "val_smape": []}
    ewma = None

    def boundary_work(reached: int, losses, fused: bool,
                      drained: bool = True) -> bool:
        """Host-side work at a step boundary: eval, ckpt, hooks, preemption.

        ``reached`` is the number of dispatched steps; ``losses`` the
        per-step losses read since the previous call, in step order. Not
        ``drained``: the per-step engine still holds losses of steps before
        ``reached`` in flight, so only the losses are recorded and the work
        that reads the params waits for its next drain.
        Returns True when the trainer should stop (preemption).
        """
        history["loss"].extend(float(l) for l in losses)
        if not drained:
            return False
        if reached % cfg.eval_every == 0 or reached == cfg.n_steps:
            with spans.span("fit.eval"):
                vs = float(val_smape(params))
            history["val_smape"].append((reached, vs))
            if ckpt is not None:
                ckpt.save(reached, (params, opt_state), metric=vs)
        elif ckpt is not None and reached % cfg.ckpt_every == 0:
            ckpt.save(reached, (params, opt_state))
        if hooks and "on_step" in hooks:
            # fused engine: the last completed step index + the segment's
            # loss array (always an array, even for a length-1 segment, so
            # hooks see one stable type); per-step engine: a float per
            # step, the pre-existing contract
            hooks["on_step"](reached - 1,
                             losses if fused else float(losses[-1]),
                             params)
        if pre.requested:
            log.warning("preemption requested at step %d; checkpointing",
                        reached)
            if ckpt is not None:
                ckpt.save(reached, (params, opt_state))
            return True
        return False

    def track_time(first_step: int, dt_per_step: float, k: int):
        nonlocal ewma
        ewma = dt_per_step if ewma is None else 0.9 * ewma + 0.1 * dt_per_step
        if first_step > 5 and dt_per_step > cfg.straggler_factor * ewma:
            log.warning("straggler step %d (x%d): %.3fs/step vs ewma %.3fs",
                        first_step, k, dt_per_step, ewma)

    # an on_step hook may retain the params tree it is handed; donation
    # would delete those buffers at the next dispatch, so hooks opt the
    # engines out of it (the pre-existing undonated behavior)
    donate = not (hooks and "on_step" in hooks)
    try:
        if cfg.series_chunk and cfg.series_chunk > 0:
            # chunk-resident reference engine: walk the *chunk-major*
            # schedule (chunk-pure batches, permuted visit order) with the
            # full table still on device -- the exact trajectory the
            # streaming HostStateTable path must reproduce, via the same
            # fused superstep fed global row indices (lo + local idx)
            superstep_fn = make_superstep_fn(step_fn, donate=donate)
            log.info("chunk-resident reference engine: series_chunk=%d",
                     cfg.series_chunk)
            stop = False
            for v in chunk_visit_plan(n, cfg.series_chunk, cfg.batch_size,
                                      start_step, cfg.n_steps, seed=cfg.seed):
                for step, k in segment_steps(
                        v.step, v.step + v.n_steps, cfg.scan_steps,
                        cfg.eval_every, cfg.ckpt_every):
                    on = spans.recording()
                    with spans.span("fit.step", leaf=False, on=on, k=k):
                        with spans.span("fit.index", on=on):
                            sched = jnp.asarray(v.lo + chunk_batch_schedule(
                                v.hi - v.lo, v.batch_size, v.epoch,
                                v.chunk_id, v.start_k + (step - v.step), k,
                                seed=cfg.seed))
                        t0 = time.perf_counter()
                        with spans.span("fit.dispatch", on=on):
                            params, opt_state, losses = superstep_fn(
                                params, opt_state, sched)
                        with spans.span("fit.loss_sync", on=on):
                            losses = np.asarray(losses)
                        track_time(step, (time.perf_counter() - t0) / k, k)
                    with spans.span("fit.boundary", leaf=False, on=on):
                        stop = boundary_work(step + k, losses, fused=True)
                    if stop:
                        break
                if stop:
                    break
        elif cfg.scan_steps > 1:
            # fused engine: K-step donated supersteps over the on-device
            # schedule; host syncs (and eval/ckpt/hooks) only at boundaries
            superstep_fn = make_superstep_fn(step_fn, donate=donate)
            log.info("fused superstep engine: scan_steps=%d%s",
                     cfg.scan_steps,
                     ", sparse per-series adam" if cfg.sparse_adam else "")
            for step, k in segment_steps(start_step, cfg.n_steps,
                                         cfg.scan_steps, cfg.eval_every,
                                         cfg.ckpt_every):
                on = spans.recording()
                with spans.span("fit.step", leaf=False, on=on, k=k):
                    with spans.span("fit.index", on=on):
                        sched = jnp.asarray(
                            batch_schedule(n, bs, step, k, seed=cfg.seed))
                    t0 = time.perf_counter()
                    with spans.span("fit.dispatch", on=on):
                        params, opt_state, losses = superstep_fn(
                            params, opt_state, sched)
                    with spans.span("fit.loss_sync", on=on):
                        losses = np.asarray(losses)   # the one host sync
                    track_time(step, (time.perf_counter() - t0) / k, k)
                with spans.span("fit.boundary", leaf=False, on=on):
                    stop = boundary_work(step + k, losses, fused=True)
                if stop:
                    break
        else:
            # per-step engine: read each loss IN_FLIGHT steps late, so the
            # host's next steps queue behind the device's; drain (read every
            # pending loss) where the params are used next: before an eval
            # or checkpoint boundary, on a preemption request, and on every
            # step that an on_step hook watches
            perstep_fn = make_perstep_fn(step_fn, donate=donate)
            depth = 0 if hooks and "on_step" in hooks else IN_FLIGHT
            pending = collections.deque()       # (step, loss) not yet read
            t_read = 0.0
            for step in range(start_step, cfg.n_steps):
                on = spans.recording()
                with spans.span("fit.step", leaf=False, on=on, k=1):
                    with spans.span("fit.index", on=on):
                        idx = jnp.asarray(
                            batch_indices(n, bs, step, seed=cfg.seed))
                    if not pending:
                        t_read = time.perf_counter()
                    with spans.span("fit.dispatch", on=on):
                        params, opt_state, loss = perstep_fn(
                            params, opt_state, idx)
                    if depth:
                        loss.copy_to_host_async()
                    pending.append((step, loss))
                    drain = (not depth or pre.requested or step + 1 ==
                             next_boundary(step, cfg.n_steps, cfg.eval_every,
                                           cfg.ckpt_every))
                    read = []
                    while len(pending) > (0 if drain else depth):
                        done, loss = pending.popleft()
                        with spans.span("fit.loss_sync", on=on,
                                        lag=len(pending)):
                            read.append(np.asarray(loss))
                        now = time.perf_counter()
                        track_time(done, now - t_read, 1)
                        t_read = now
                with spans.span("fit.boundary", leaf=False, on=on):
                    stop = boundary_work(step + 1, read, fused=False,
                                         drained=drain)
                if stop:
                    break
    finally:
        pre.uninstall()

    return {"params": params, "opt_state": opt_state, "history": history,
            "resumed_from": start_step}


def _resolve_train_mesh(cfg: TrainConfig, mesh):
    """Resolve ``cfg.data_parallel`` into a series mesh (None = 1 device)."""
    if mesh is None and cfg.data_parallel and cfg.data_parallel > 1:
        from repro.sharding.series import make_series_mesh

        mesh = make_series_mesh(cfg.data_parallel)
    if mesh is not None and mesh.devices.size == 1:
        mesh = None  # 1-device mesh: identical math, skip the shard_map hop
    return mesh


def _train_chunked(
    mcfg: ESRNNConfig,
    data: PreparedData,
    cfg: TrainConfig,
    *,
    params=None,
    hooks: Optional[Dict[str, Callable]] = None,
    mesh=None,
) -> Dict:
    """The streaming chunked fit: out-of-core HW table, resident head.

    The N-series state -- HW rows, their sparse-Adam moments, the ``t_hw``
    clocks -- lives in a host :class:`~repro.train.host_table.HostStateTable`;
    only one ``series_chunk``-row slice (plus its slice of the training
    tensors) is on device at a time. Shared head weights, their moments and
    the global ``step`` scalar persist on device across chunks. Per epoch the
    chunks are visited in permuted order with chunk-pure batches
    (:func:`~repro.data.pipeline.chunk_visit_plan`); within a visit the
    donated chunk superstep runs the ordinary fused segments. The next
    visit's H2D transfers are enqueued before the current visit's compute is
    drained (double buffering via JAX async dispatch), and a retiring chunk
    is written back D2H only when the rows actually change hands.

    Because ``t_hw`` carries *global* last-touch steps and the Adam ``step``
    scalar is global, the per-chunk sparse updates are exact: this walks the
    same trajectory as ``chunk_resident=True`` (the full-table debug
    reference) bit-for-bit on one backend. Eval streams chunks through
    ``smape_terms``; checkpoints carry the same ``(params, opt_state)`` tree
    as a resident sparse fit (table leaves host-side, sharded files), so the
    two modes resume into each other. Returned ``params["hw"]`` leaves are
    host numpy.
    """
    mesh = _resolve_train_mesh(cfg, mesh)
    n = data.n_series
    per_chunk, _ = chunk_layout(n, cfg.series_chunk, cfg.batch_size)
    if mesh is not None:
        from repro.sharding.series import check_series_divisible

        for _, _, bs_c, _ in per_chunk:
            check_series_divisible(bs_c, mesh)
        log.info("chunked + series-data-parallel: %d chunks over %d devices",
                 len(per_chunk), mesh.devices.size)
    cfg_adam = AdamConfig(
        lr=cfg.lr,
        clip_norm=cfg.clip_norm,
        group_lr={"per_series": cfg.per_series_lr_mult, "default": 1.0},
    )
    frozen = frozen_param_groups(mcfg)

    # shared weights: the head init never sees n_series, so a 1-row init is
    # bit-identical to the resident esrnn_init(key, mcfg, n) shared leaves
    seed_params = esrnn_init(jax.random.PRNGKey(cfg.seed), mcfg, 1)
    if params is not None:
        # warm start: adopt the caller's rows into the host table (copied --
        # absorb writes in place) and copy the shared leaves (donation)
        table = HostStateTable.from_state(params, with_moments=True)
        shared = {k: jnp.array(v, copy=True)
                  for k, v in params.items() if k != "hw"}
    else:
        table = HostStateTable.init(
            n, mcfg.seasonality, seasonality2=mcfg.seasonality2,
            dtype=np.dtype(mcfg.dtype))
        shared = {k: v for k, v in seed_params.items() if k != "hw"}
    shared_train, _ = split_frozen(shared, frozen)
    if frozen:
        log.info("head %r freezes param group(s) %s: training %s + hw only",
                 mcfg.head, sorted(frozen),
                 sorted(k for k in shared_train))
    sh_opt = adam_init(shared_train)
    mu_sh, nu_sh, step_scalar = sh_opt["mu"], sh_opt["nu"], sh_opt["step"]
    log.info("streaming chunked fit: N=%d series_chunk=%d (%d chunks), "
             "host table %.1f MB", n, cfg.series_chunk, len(per_chunk),
             table.nbytes() / 1e6)

    def full_state():
        """The checkpoint/return tree: same structure as a resident sparse
        fit (restores interchangeably), table leaves host numpy."""
        return ({"hw": table.hw, **shared},
                {"mu": {"hw": table.mu_hw, **mu_sh},
                 "nu": {"hw": table.nu_hw, **nu_sh},
                 "step": step_scalar, "t_hw": table.t_hw})

    start_step = 0
    ckpt = Checkpointer(cfg.ckpt_dir, keep=cfg.keep) if cfg.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        is_table = lambda path: any(
            getattr(e, "key", getattr(e, "name", None)) in ("hw", "t_hw")
            for e in path)
        try:
            start_step, (p_full, o_full) = ckpt.restore(
                full_state(), host_paths=is_table)
        except ValueError as e:
            if "tree structure mismatch" not in str(e):
                raise
            raise ValueError(
                f"cannot resume from {cfg.ckpt_dir}: {e}. Chunked fits "
                "carry the sparse-Adam state; a checkpoint written with "
                "sparse_adam=False (dense moments) is not interchangeable "
                "-- resume with the original setting") from e
        table = HostStateTable(
            p_full["hw"], mu_hw=o_full["mu"]["hw"], nu_hw=o_full["nu"]["hw"],
            t_hw=o_full["t_hw"])
        shared = {k: v for k, v in p_full.items() if k != "hw"}
        mu_sh = {k: v for k, v in o_full["mu"].items() if k != "hw"}
        nu_sh = {k: v for k, v in o_full["nu"].items() if k != "hw"}
        step_scalar = o_full["step"]
        log.info("resumed from step %d", start_step)

    y_np = np.asarray(data.train)
    cats_np = np.asarray(data.cats)
    mask_np = np.asarray(data.mask)
    val_np = np.asarray(data.val_target)
    h_val = min(mcfg.output_size, val_np.shape[1])

    step_fn = make_chunk_step_fn(mcfg, cfg_adam, mesh=mesh, frozen=frozen)
    donate = not (hooks and "on_step" in hooks)
    superstep_fn = make_chunk_superstep_fn(step_fn, donate=donate)

    @jax.jit
    def _val_terms(sh, hw_c, y_c, cats_c, tgt_c):
        fc = esrnn_forecast(mcfg, {"hw": hw_c, **sh}, y_c, cats_c)
        return L.smape_terms(fc[:, :h_val], tgt_c[:, :h_val])

    def streamed_val_smape() -> float:
        """Validation sMAPE without full-table residency: stream every chunk
        through the forecast, accumulate the exact sum/count terms."""
        s = c = 0.0
        for lo, hi, _, _ in per_chunk:
            hw_c = jax.tree_util.tree_map(
                lambda a: jax.device_put(a[lo:hi]), table.hw)
            ds, dc = _val_terms(shared, hw_c, jnp.asarray(y_np[lo:hi]),
                                jnp.asarray(cats_np[lo:hi]),
                                jnp.asarray(val_np[lo:hi]))
            s += float(ds)
            c += float(dc)
        return 200.0 * s / max(c, 1.0)

    def _stage(lo: int, hi: int) -> Dict:
        """Enqueue one chunk's H2D transfers: table rows + data slices."""
        return {"state": table.device_slice(lo, hi),
                "y": jax.device_put(y_np[lo:hi]),
                "cats": jax.device_put(cats_np[lo:hi]),
                "mask": jax.device_put(mask_np[lo:hi])}

    pre = PreemptionHandler()
    pre.install()
    history = {"loss": [], "val_smape": []}
    ewma = None
    stop = False

    def track_time(first_step: int, dt_per_step: float, k: int):
        nonlocal ewma
        ewma = dt_per_step if ewma is None else 0.9 * ewma + 0.1 * dt_per_step
        if first_step > 5 and dt_per_step > cfg.straggler_factor * ewma:
            log.warning("straggler step %d (x%d): %.3fs/step vs ewma %.3fs",
                        first_step, k, dt_per_step, ewma)

    def _sync_shared(cparams, copt):
        nonlocal shared, mu_sh, nu_sh, step_scalar
        shared = {k: x for k, x in cparams.items() if k != "hw"}
        mu_sh = {k: x for k, x in copt["mu"].items() if k != "hw"}
        nu_sh = {k: x for k, x in copt["nu"].items() if k != "hw"}
        step_scalar = copt["step"]

    def _retire(v, cparams, copt):
        """Write the visit's rows back into the host table + sync shared."""
        _sync_shared(cparams, copt)
        table.absorb(v.lo, v.hi, {
            "hw": cparams["hw"], "mu": copt["mu"]["hw"],
            "nu": copt["nu"]["hw"], "t_hw": copt["t_hw"]})

    def chunk_boundary(v, reached, losses, cparams, copt):
        nonlocal stop
        history["loss"].extend(float(l) for l in losses)
        do_eval = reached % cfg.eval_every == 0 or reached == cfg.n_steps
        do_ckpt = ckpt is not None and (
            do_eval or reached % cfg.ckpt_every == 0)
        if do_eval or do_ckpt or pre.requested:
            # checkpoint/eval see the chunk's latest rows through the table
            _retire(v, cparams, copt)
        if do_eval:
            with spans.span("fit.eval"):
                vs = streamed_val_smape()
            history["val_smape"].append((reached, vs))
            if ckpt is not None:
                ckpt.save(reached, full_state(), metric=vs,
                          shard_rows=cfg.series_chunk)
        elif do_ckpt:
            ckpt.save(reached, full_state(), shard_rows=cfg.series_chunk)
        if hooks and "on_step" in hooks:
            hooks["on_step"](reached - 1, losses, cparams)
        if pre.requested:
            log.warning("preemption requested at step %d; checkpointing",
                        reached)
            if ckpt is not None:
                ckpt.save(reached, full_state(), shard_rows=cfg.series_chunk)
            stop = True

    visits = list(chunk_visit_plan(n, cfg.series_chunk, cfg.batch_size,
                                   start_step, cfg.n_steps, seed=cfg.seed))
    staged = _stage(visits[0].lo, visits[0].hi) if visits else None
    try:
        for i, v in enumerate(visits):
            cur = staged
            staged = None
            cparams = {"hw": cur["state"]["hw"], **shared}
            copt = {"mu": {"hw": cur["state"]["mu"], **mu_sh},
                    "nu": {"hw": cur["state"]["nu"], **nu_sh},
                    "step": step_scalar, "t_hw": cur["state"]["t_hw"]}
            nxt = visits[i + 1] if i + 1 < len(visits) else None
            if nxt is not None and (nxt.lo, nxt.hi) != (v.lo, v.hi):
                # double-buffer: enqueue the next chunk's H2D now, so it
                # rides under this visit's compute. Same-row next visits
                # skip it -- their rows would be stale -- and instead carry
                # the retiring device state forward directly.
                staged = _stage(nxt.lo, nxt.hi)
            for step, k in segment_steps(
                    v.step, v.step + v.n_steps, cfg.scan_steps,
                    cfg.eval_every, cfg.ckpt_every):
                on = spans.recording()
                with spans.span("fit.step", leaf=False, on=on, k=k):
                    with spans.span("fit.index", on=on):
                        sched = jnp.asarray(chunk_batch_schedule(
                            v.hi - v.lo, v.batch_size, v.epoch, v.chunk_id,
                            v.start_k + (step - v.step), k, seed=cfg.seed))
                    t0 = time.perf_counter()
                    with spans.span("fit.dispatch", on=on):
                        cparams, copt, losses = superstep_fn(
                            cparams, copt, cur["y"], cur["cats"], cur["mask"],
                            sched)
                    with spans.span("fit.loss_sync", on=on):
                        losses = np.asarray(losses)  # the one host sync
                    track_time(step, (time.perf_counter() - t0) / k, k)
                with spans.span("fit.boundary", leaf=False, on=on):
                    chunk_boundary(v, step + k, losses, cparams, copt)
                if stop:
                    break
            if stop:
                break
            if nxt is not None and (nxt.lo, nxt.hi) == (v.lo, v.hi):
                # same rows next visit (e.g. a single chunk covering all N):
                # no round-trip, hand the device state straight across
                staged = {"state": {"hw": cparams["hw"],
                                    "mu": copt["mu"]["hw"],
                                    "nu": copt["nu"]["hw"],
                                    "t_hw": copt["t_hw"]},
                          "y": cur["y"], "cats": cur["cats"],
                          "mask": cur["mask"]}
                _sync_shared(cparams, copt)
            else:
                _retire(v, cparams, copt)
    finally:
        pre.uninstall()

    p_full, o_full = full_state()
    return {"params": p_full, "opt_state": o_full, "history": history,
            "resumed_from": start_step}


def train_from_spec(
    spec,
    data: PreparedData,
    *,
    ckpt_dir: Optional[str] = None,
    n_steps: Optional[int] = None,
    params=None,
    hooks: Optional[Dict[str, Callable]] = None,
    mesh=None,
) -> Dict:
    """Spec-driven entry point: ``ForecastSpec`` in, trained params out.

    This is the path ``repro.forecast.ESRNNForecaster.fit`` and the
    ``repro.launch.forecast`` CLI use; the two-group learning rates come
    straight from the spec's first-class ``rnn_lr`` / ``hw_lr`` fields.
    ``spec.data_parallel`` (or an explicit ``mesh``) turns on series-sharded
    multi-device training.
    """
    cfg = TrainConfig.from_spec(spec, ckpt_dir=ckpt_dir, n_steps=n_steps)
    return train_esrnn(spec.model, data, cfg, params=params, hooks=hooks,
                       mesh=mesh)
