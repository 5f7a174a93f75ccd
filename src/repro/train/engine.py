"""Fused scan-over-steps training engine for the ES-RNN.

The per-step trainer is dispatch-bound: one jitted step per Python iteration,
with its batch index and loss read, costs host time every step. Reading each
loss a few steps late overlaps that host time with queued device steps, but
a step shorter than the host path still leaves the device idle between
launches (the BENCH_PR3 device sweep, with a host round-trip every step,
showed 8 devices only ~1.4x faster than 1 -- overhead, not compute). This
module removes the Python loop from the hot path the same way the paper
removed Smyl's per-series C++ loop: compile K steps into one donated
*superstep*.

Three pieces:

* :func:`make_step_fn` -- the pure single training step
  ``(params, opt_state, idx) -> (params, opt_state, loss)``, parameterized
  over the loss path (single-device / ``shard_map`` series-data-parallel /
  Pallas kernels -- the config decides inside ``esrnn_loss_fn``) and the
  optimizer path (dense Adam over the full per-series table, or the sparse
  segment update of :func:`~repro.train.optimizer.adam_update_sparse` that
  touches only the batch's rows).
* :func:`make_superstep_fn` -- ``jax.lax.scan`` of that step over a
  ``(K, B)`` on-device batch-index schedule, jitted with
  ``donate_argnums=(params, opt_state)`` so the optimizer state ping-pongs
  in place instead of being copied every step. Returns the K per-step losses
  as one array; the host syncs once per superstep, which is where eval,
  checkpointing, the straggler EWMA, and ``on_step`` hooks run.
* :func:`segment_steps` -- chops ``[start_step, n_steps)`` into superstep
  segments that land exactly on every eval/checkpoint boundary, so the fused
  loop fires them at the same global steps as the per-step loop, and a
  mid-run resume (any ``start_step``) realigns with the same boundaries via
  the stateless schedule.

The scan carries no data -- the index schedule is materialized once per
segment by :func:`~repro.data.pipeline.batch_schedule` and the series tensors
are closed over as device constants -- so the only per-step work left is the
computation itself.
"""

from __future__ import annotations

from typing import Callable, FrozenSet, Iterator, Tuple

import jax
import jax.numpy as jnp

from repro.core.esrnn import (
    ESRNNConfig, combine_series, esrnn_loss_fn, gather_series,
    partition_series,
)
from repro.train.optimizer import (
    AdamConfig, adam_update, adam_update_sparse, esrnn_group_fn,
)

StepFn = Callable


def split_frozen(params, frozen: FrozenSet[str]):
    """Split a params dict by top-level key into (trainable, frozen).

    The head registry (``repro.core.heads.frozen_param_groups``) declares
    which top-level groups a head keeps untrainable (e.g. the esn head's
    ``"rnn"`` reservoir). The step functions differentiate and run Adam over
    the trainable subtree only, closing over the frozen one -- XLA then
    never builds the frozen groups' weight-gradient computations, which is
    where the esn head's near-free fits come from. Optimizer state is built
    over the same trainable subtree (``adam_init(split_frozen(p, f)[0])``),
    so checkpoints carry no moments for weights that never move. With
    ``frozen`` empty this is the identity partition and every trajectory is
    bit-for-bit what it was before frozen groups existed.
    """
    return ({k: v for k, v in params.items() if k not in frozen},
            {k: v for k, v in params.items() if k in frozen})


def make_step_fn(
    mcfg: ESRNNConfig,
    cfg_adam: AdamConfig,
    y_all,
    cats_all,
    mask_all,
    *,
    mesh=None,
    sparse: bool = False,
    frozen: FrozenSet[str] = frozenset(),
    compress: bool = False,
) -> StepFn:
    """Build the pure training step the per-step loop and the scan share.

    ``y_all``/``cats_all``/``mask_all`` are the full on-device series tensors
    (closed over; the step only receives the batch's row indices). ``mesh``
    switches the loss to the ``shard_map``-wrapped exact-masked-mean
    ``esrnn_loss_dp``; ``sparse`` switches the per-series update to the
    segment path: gradients are taken w.r.t. the *gathered* batch rows (so
    the backward pass never scatters a zero-padded table-sized gradient) and
    Adam touches only those rows, with closed-form moment catch-up.

    ``frozen`` names top-level param groups excluded from training (the
    config head's declaration -- see :func:`split_frozen`): the step
    differentiates and updates the trainable subtree only, and the caller's
    ``opt_state`` must cover exactly that subtree. The returned step still
    takes and returns the *full* params dict -- frozen groups ride through
    unchanged -- so the checkpoint/save/predict surface stays head-agnostic.

    ``compress`` turns on error-feedback int8 compression of the *shared*
    weight gradients (``repro.train.grad_compression``) before Adam sees
    them: the values entering the (GSPMD-emitted) gradient all-reduce are
    int8-decodable, and the quantization residual is carried in the step
    state and added back next step, which keeps convergence (Karimireddy et
    al. 2019). The per-series HW rows are data-sharded and never
    all-reduced, so they stay exact. With ``compress`` the step's
    ``opt_state`` is ``(adam_state, error_state)`` where ``error_state``
    covers the shared trainable groups (``init_error_state``). Dense
    optimizer path only.
    """
    if sparse and compress:
        raise ValueError(
            "compress=True requires the dense optimizer path: the sparse "
            "segment update only ever touches per-series HW rows locally, "
            "so there is no shared-gradient exchange to compress")
    if mesh is not None:
        from repro.sharding.series import esrnn_loss_dp

        def loss_fn(pb, yb, cb, mb):
            return esrnn_loss_dp(mcfg, pb, yb, cb, mb, mesh=mesh)
    else:
        def loss_fn(pb, yb, cb, mb):
            return esrnn_loss_fn(mcfg, pb, yb, cb, mb)

    def step(params, opt_state, idx):
        yb = y_all[idx]
        cb = cats_all[idx]
        mb = mask_all[idx]
        p_train, p_froz = split_frozen(params, frozen)

        if sparse:
            hw_rows, shared = partition_series(params, idx)
            sh_train, sh_froz = split_frozen(shared, frozen)

            def batch_loss(hw_b, sh):
                return loss_fn(
                    combine_series(hw_b, {**sh, **sh_froz}), yb, cb, mb)

            loss, (g_hw, g_sh) = jax.value_and_grad(
                batch_loss, argnums=(0, 1))(hw_rows, sh_train)
            grads = combine_series(g_hw, g_sh)
            p_train, opt_state = adam_update_sparse(
                grads, opt_state, p_train, cfg_adam, idx=idx,
                group_fn=esrnn_group_fn)
        else:
            def batch_loss(p):
                # differentiating through the gather scatters the gradient
                # back over the full N-row table (dense Adam consumes it)
                return loss_fn(gather_series({**p, **p_froz}, idx), yb, cb, mb)

            loss, grads = jax.value_and_grad(batch_loss)(p_train)
            if compress:
                from repro.train.grad_compression import compress_tree_int8

                adam_state, err = opt_state
                # deterministic per-batch quantization noise: fold the batch
                # identity into a fixed key, so a resumed/refused run
                # re-draws the same noise at the same schedule position
                key = jax.random.fold_in(
                    jax.random.PRNGKey(0), jnp.sum(idx).astype(jnp.uint32))
                g_shared = {k: v for k, v in grads.items() if k != "hw"}
                g_shared, err = compress_tree_int8(g_shared, err, key)
                grads = {**grads, **g_shared}
                p_train, adam_state = adam_update(
                    grads, adam_state, p_train, cfg_adam,
                    group_fn=esrnn_group_fn)
                opt_state = (adam_state, err)
            else:
                p_train, opt_state = adam_update(
                    grads, opt_state, p_train, cfg_adam,
                    group_fn=esrnn_group_fn)
        return {**p_train, **p_froz}, opt_state, loss

    return step


def make_online_step_fn(
    mcfg: ESRNNConfig,
    cfg_adam: AdamConfig,
    *,
    sparse: bool = True,
    frozen: FrozenSet[str] = frozenset(),
) -> StepFn:
    """Training step over an *ad-hoc* batch: the serving fine-tune hook.

    Unlike :func:`make_step_fn`, which closes over the full training tensors
    and receives only row indices, here the batch arrives as arguments --
    the forecast server builds ``(y, cats, mask)`` from its online store's
    recently-observed history tails at call time, and ``rows`` names the
    per-series HW-table rows those batch rows correspond to. With
    ``sparse=True`` (the intended serving shape) gradients are taken w.r.t.
    the gathered rows and :func:`~repro.train.optimizer.adam_update_sparse`
    touches exactly those rows with closed-form moment catch-up -- a few
    incremental steps on live series never pay for the full table. The
    returned step is pure; the caller jits it (shapes vary with the
    fine-tune batch, so the cache discipline is the caller's).
    """

    def step(params, opt_state, y, cats, mask, rows):
        p_train, p_froz = split_frozen(params, frozen)
        if sparse:
            hw_rows, shared = partition_series(params, rows)
            sh_train, sh_froz = split_frozen(shared, frozen)

            def batch_loss(hw_b, sh):
                return esrnn_loss_fn(
                    mcfg, combine_series(hw_b, {**sh, **sh_froz}), y, cats,
                    mask)

            loss, (g_hw, g_sh) = jax.value_and_grad(
                batch_loss, argnums=(0, 1))(hw_rows, sh_train)
            grads = combine_series(g_hw, g_sh)
            p_train, opt_state = adam_update_sparse(
                grads, opt_state, p_train, cfg_adam, idx=rows,
                group_fn=esrnn_group_fn)
        else:
            def batch_loss(p):
                return esrnn_loss_fn(
                    mcfg, gather_series({**p, **p_froz}, rows), y, cats, mask)

            loss, grads = jax.value_and_grad(batch_loss)(p_train)
            p_train, opt_state = adam_update(
                grads, opt_state, p_train, cfg_adam, group_fn=esrnn_group_fn)
        return {**p_train, **p_froz}, opt_state, loss

    return step


def make_perstep_fn(step_fn: StepFn, *, donate: bool = True):
    """The fallback per-step engine: one donated jit per call.

    Donating ``(params, opt_state)`` lets XLA update the full per-series HW
    table and Adam moments in place instead of allocating fresh copies every
    step (the old un-donated path did). The caller must treat the passed-in
    arrays as consumed -- the trainer rebinds them from the return value.
    ``donate=False`` opts out (the trainer does when an ``on_step`` hook is
    registered, because a hook may legitimately retain the params tree it
    is handed, and donation would delete those buffers one step later).
    """
    return jax.jit(step_fn, donate_argnums=(0, 1) if donate else ())


def make_superstep_fn(step_fn: StepFn, *, donate: bool = True):
    """Fuse K steps into one donated ``lax.scan`` superstep.

    ``(params, opt_state, idx_schedule(K, B)) ->
    (params, opt_state, losses(K,))`` -- one dispatch, one host sync, K
    optimizer updates. Compiles once per distinct K (the trainer's segment
    planner produces at most a handful of K values per run). ``donate``
    as in :func:`make_perstep_fn`.
    """
    def superstep(params, opt_state, idx_schedule):
        def body(carry, idx):
            p, o = carry
            p, o, loss = step_fn(p, o, idx)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), idx_schedule)
        return params, opt_state, losses

    return jax.jit(superstep, donate_argnums=(0, 1) if donate else ())


def make_chunk_step_fn(
    mcfg: ESRNNConfig,
    cfg_adam: AdamConfig,
    *,
    mesh=None,
    frozen: FrozenSet[str] = frozenset(),
) -> StepFn:
    """The chunked-streaming training step: data arrives as arguments.

    Identical math to the ``sparse=True`` branch of :func:`make_step_fn` --
    gathered-row gradients, segment Adam with closed-form moment catch-up --
    but ``(y_c, cats_c, mask_c)`` are the *current chunk's* series tensors
    passed as jit arguments instead of closed-over device constants, so one
    compiled executable serves every chunk of the same shape as the trainer
    streams shards out of the host table. ``params``/``opt_state`` here are
    the chunk-assembled trees: the HW leaves hold only the chunk's rows
    (``idx`` is chunk-local) while the shared head weights and the global
    ``step`` scalar persist across chunks; ``t_hw`` carries global last-touch
    steps, which is what makes the per-chunk sparse updates exact.
    """
    if mesh is not None:
        from repro.sharding.series import esrnn_loss_dp

        def loss_fn(pb, yb, cb, mb):
            return esrnn_loss_dp(mcfg, pb, yb, cb, mb, mesh=mesh)
    else:
        def loss_fn(pb, yb, cb, mb):
            return esrnn_loss_fn(mcfg, pb, yb, cb, mb)

    def step(params, opt_state, y_c, cats_c, mask_c, idx):
        yb = y_c[idx]
        cb = cats_c[idx]
        mb = mask_c[idx]
        p_train, p_froz = split_frozen(params, frozen)
        hw_rows, shared = partition_series(params, idx)
        sh_train, sh_froz = split_frozen(shared, frozen)

        def batch_loss(hw_b, sh):
            return loss_fn(
                combine_series(hw_b, {**sh, **sh_froz}), yb, cb, mb)

        loss, (g_hw, g_sh) = jax.value_and_grad(
            batch_loss, argnums=(0, 1))(hw_rows, sh_train)
        grads = combine_series(g_hw, g_sh)
        p_train, opt_state = adam_update_sparse(
            grads, opt_state, p_train, cfg_adam, idx=idx,
            group_fn=esrnn_group_fn)
        return {**p_train, **p_froz}, opt_state, loss

    return step


def make_chunk_superstep_fn(step_fn: StepFn, *, donate: bool = True):
    """Donated ``lax.scan`` superstep over one chunk's batch schedule.

    ``(params, opt_state, y_c, cats_c, mask_c, idx_schedule(K, B)) ->
    (params, opt_state, losses(K,))``. The chunk state ping-pongs in place
    (donated) while the data tensors ride through as loop invariants; the
    trainer re-dispatches the same executable for every equal-shaped chunk
    visit, so a streamed epoch costs the same compile budget as a resident
    one plus at most a ragged-tail variant.
    """
    def superstep(params, opt_state, y_c, cats_c, mask_c, idx_schedule):
        def body(carry, idx):
            p, o = carry
            p, o, loss = step_fn(p, o, y_c, cats_c, mask_c, idx)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), idx_schedule)
        return params, opt_state, losses

    return jax.jit(superstep, donate_argnums=(0, 1) if donate else ())


def lower_superstep(step_fn: StepFn, params, opt_state, idx_schedule, *,
                    donate: bool = True):
    """AOT-lower the donated superstep for the given argument shapes.

    The graph auditor's donation lint needs the *compiled* artifact (its
    ``input_output_alias`` header proves which donated buffers actually
    alias); ``make_superstep_fn`` only returns the jitted callable, whose
    executable is not inspectable until traced. Returns the ``Lowered``
    object -- call ``.compile()`` for the executable, ``.as_text()`` for
    the pre-optimization module.
    """
    return make_superstep_fn(step_fn, donate=donate).lower(
        params, opt_state, idx_schedule)


def next_boundary(step: int, n_steps: int, *everys: int) -> int:
    """First step strictly after ``step`` where eval/ckpt may fire."""
    cands = [n_steps]
    for e in everys:
        if e and e > 0:
            cands.append((step // e + 1) * e)
    return min(c for c in cands if c > step)


def segment_steps(
    start_step: int,
    n_steps: int,
    scan_steps: int,
    *everys: int,
) -> Iterator[Tuple[int, int]]:
    """Yield ``(step, K)`` superstep segments covering [start_step, n_steps).

    Every eval/checkpoint boundary (multiples of the ``everys``, plus
    ``n_steps`` itself) coincides with a segment end, so host-side work fires
    at exactly the same global steps as the per-step loop would -- and a
    resumed run (arbitrary ``start_step`` from a checkpoint) re-aligns with
    the same absolute boundaries, because segments are planned in global
    step coordinates, not relative to the resume point.
    """
    step = start_step
    while step < n_steps:
        limit = next_boundary(step, n_steps, *everys)
        k = min(max(1, scan_steps), limit - step)
        yield step, k
        step += k
