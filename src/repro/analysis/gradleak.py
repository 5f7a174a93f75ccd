"""Gradient-leak lint: prove frozen param groups stay gradient-free.

The ``esn`` head's performance claim is that its reservoir (the ``"rnn"``
group) never trains -- ``repro.train.engine.make_step_fn(frozen=...)``
differentiates the trainable subtree only, so XLA never builds reservoir
weight-gradient matmuls. That property was enforced empirically (reservoir
bit-equal across fits); this lint proves it *statically* on the traced step
jaxpr, per commit, with three independent checks:

1. **identity pass-through** -- every frozen leaf's output var IS its input
   var (the step returns the frozen subtree untouched; any update applied
   to it breaks the identity),
2. **no optimizer moments** -- the optimizer state pytree carries no leaf
   whose aval matches a frozen weight (moments for a frozen weight mean the
   optimizer was built over it),
3. **no gradient primitives** -- no equation anywhere in the program (all
   nested scans/pjits included) produces a frozen-weight-shaped value via a
   gradient-accumulating primitive (``dot_general`` weight-grad matmuls,
   ``add_any`` cotangent accumulation, ``reduce_sum`` bias grads,
   scatter-adds). The forward pass only *consumes* weights; values shaped
   like a weight can only be that weight's cotangent.

Check 3 identifies gradients by shape, so the probe batch must not collide
with weight shapes (a batch of ``hidden_size`` rows makes activation
cotangents ``(B, 4H)`` look like the ``(H, 4H)`` hidden weights).
:func:`probe_batch_size` picks a collision-free size; the lint also verifies
the choice and reports a finding if a collision makes check 3 inconclusive.
"""

from __future__ import annotations

import dataclasses
from typing import FrozenSet, List, Sequence, Tuple

import jax
from jax.extend import core as jcore
from jax.tree_util import tree_flatten_with_path

from repro.analysis.jaxpr_walk import aval_key, iter_eqns

# primitives that build/accumulate gradients; forward-only programs produce
# weight-shaped values through none of these (weights are only consumed)
GRAD_PRIMITIVES = frozenset(
    {"dot_general", "add_any", "reduce_sum", "scatter-add", "scatter_add"})


@dataclasses.dataclass
class Finding:
    """One invariant violation (shared by every lint in the package)."""

    lint: str
    message: str

    def to_dict(self):
        return {"lint": self.lint, "message": self.message}


def _frozen_leaf_positions(args_tree, frozen: FrozenSet[str],
                           params_index: int = 0) -> List[int]:
    """Flat indices of frozen-group leaves inside the step's argument tree.

    ``args_tree`` is the exact tuple traced (``(params, opt_state, idx)``);
    flattening order matches ``jax.make_jaxpr``'s invar order.
    """
    leaves = tree_flatten_with_path(args_tree)[0]
    out = []
    for i, (path, _) in enumerate(leaves):
        if not path or getattr(path[0], "idx", None) != params_index:
            continue
        if len(path) >= 2 and getattr(path[1], "key", None) in frozen:
            out.append(i)
    return out


def probe_batch_size(cfg, params, candidates: Sequence[int] = (5, 7, 11, 13),
                     frozen: FrozenSet[str] = frozenset()) -> int:
    """A batch size whose activation shapes cannot shadow frozen weights.

    Check 3 of the lint is shape-based: pick B such that no frozen leaf has
    B as a leading dimension (cotangents of batch activations lead with B).
    """
    frozen_dims = set()
    for name, group in params.items():
        if name in frozen:
            for leaf in jax.tree_util.tree_leaves(group):
                frozen_dims.update(leaf.shape)
    for b in candidates:
        if b not in frozen_dims:
            return b
    return max(frozen_dims) + 1


def gradient_leak_findings(step_fn, params, opt_state, idx,
                           frozen: FrozenSet[str]) -> Tuple[List[Finding], dict]:
    """Run the three static checks on one training-step function.

    Returns ``(findings, metrics)``; an empty findings list is the proof
    that no frozen group contributes gradient primitives to the step.
    """
    findings: List[Finding] = []
    closed = jax.make_jaxpr(step_fn)(params, opt_state, idx)
    jaxpr = closed.jaxpr
    args = (params, opt_state, idx)

    frozen_in = _frozen_leaf_positions(args, frozen)
    out_shape = jax.eval_shape(step_fn, params, opt_state, idx)
    frozen_out = _frozen_leaf_positions(out_shape, frozen)

    # 1. identity pass-through ------------------------------------------------
    passthrough_ok = 0
    if len(frozen_in) != len(frozen_out):
        findings.append(Finding(
            "gradient-leak",
            f"frozen groups have {len(frozen_in)} input leaves but "
            f"{len(frozen_out)} output leaves: the step does not return the "
            f"frozen subtree structurally unchanged"))
    else:
        for i, o in zip(frozen_in, frozen_out):
            if jaxpr.outvars[o] is jaxpr.invars[i]:
                passthrough_ok += 1
            else:
                findings.append(Finding(
                    "gradient-leak",
                    f"frozen leaf (invar {i}) is not passed through "
                    f"unchanged to output {o}: an update is applied to a "
                    f"frozen param group"))

    # 2. no optimizer moments over frozen weights -----------------------------
    frozen_avals = {aval_key(jaxpr.invars[i].aval) for i in frozen_in}
    opt_leaves = tree_flatten_with_path(opt_state)[0]
    for path, leaf in opt_leaves:
        keys = {getattr(p, "key", None) for p in path}
        if keys & set(frozen):
            findings.append(Finding(
                "gradient-leak",
                f"optimizer state carries moments for frozen group "
                f"{sorted(keys & set(frozen))} at {jax.tree_util.keystr(path)}"))

    # 3. no gradient primitives producing frozen-weight-shaped values --------
    # guard: the probe shapes must make frozen avals unambiguous
    trainable_avals = set()
    for i, (path, leaf) in enumerate(tree_flatten_with_path(args)[0]):
        if i not in frozen_in:
            trainable_avals.add(
                (tuple(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", ""))))
    collisions = frozen_avals & trainable_avals
    if collisions:
        findings.append(Finding(
            "gradient-leak",
            f"probe shapes are ambiguous: frozen and trainable leaves share "
            f"avals {sorted(collisions)}; pick distinct probe dimensions "
            f"(see probe_batch_size)"))

    # a weight cotangent may materialize one layout hop after the grad
    # primitive (``dot_general`` -> ``transpose`` is jax's standard weight
    # transpose rule), so track producers and treat layout ops fed by a
    # gradient primitive as gradient-producing themselves
    producer = {}
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            producer[v] = eqn.primitive.name
    _LAYOUT = {"transpose", "reshape", "convert_element_type", "copy"}

    def _is_grad_eqn(eqn) -> bool:
        if eqn.primitive.name in GRAD_PRIMITIVES:
            return True
        if eqn.primitive.name in _LAYOUT:
            return any(producer.get(iv) in GRAD_PRIMITIVES
                       for iv in eqn.invars
                       if not isinstance(iv, jcore.Literal))
        return False

    grad_hits = 0
    for eqn in iter_eqns(jaxpr):
        if not _is_grad_eqn(eqn):
            continue
        for v in eqn.outvars:
            if aval_key(v.aval) in frozen_avals:
                grad_hits += 1
                findings.append(Finding(
                    "gradient-leak",
                    f"gradient primitive `{eqn.primitive.name}` produces a "
                    f"frozen-weight-shaped value {aval_key(v.aval)}: a "
                    f"frozen group's weight gradient is being built"))

    metrics = {
        "frozen_leaves": len(frozen_in),
        "passthrough_ok": passthrough_ok,
        "grad_primitive_hits": grad_hits,
        "eqns_scanned": sum(1 for _ in iter_eqns(jaxpr)),
    }
    return findings, metrics
