"""The target network held in the dtype its forward reads.

Under the bf16 policy the forward casts most weights to bf16 before it uses
them. The online parameters stay float32 (Adam updates them), but the target
changes only at a sync (``train_step.sync_target``): a target leaf that its
forward only ever reads as bf16 is held in bf16, cast once a sync instead of
once a step, with half the bytes resident and read. A weight cast to bf16
before any use gives the same operand whether it was stored as float32 or
as bf16 cast at the sync, so the target's arithmetic does not change. The
program XLA builds around it does (no cast fused into the products): on the
CPU the target's Q values are the float32 tree's bit for bit, on a TPU they
differ as any two programs of the same bf16 arithmetic do, by the rounding
of bf16 activations (PERF.md, Findings).

Which leaves is read off the forward's own program (``target_plan``), never
off names: a leaf is narrowed where every use of it converts it to the
compute dtype. The norms' weights, the routers' kernels (float32 products),
the expert bias, the short convolutions' taps and any leaf the forward does
not read stay float32. So do the kernels of convolutions
(``conv_general_dilated``): they weigh some 80 K parameters, and a float32
reader of the target (the benchmark's plain reference) hands them to
``lax.conv_general_dilated`` beside float32 frames, which takes one dtype
(a rule to drop once that reference takes a bf16 kernel: ROADMAP.md, S7).
"""

import weakref
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# primitives that move a value without arithmetic, so that a cast commutes
# with them: a use through one of them is the uses of its result
_MOVES = frozenset({"reshape", "transpose", "squeeze", "expand_dims",
                    "broadcast_in_dim", "slice", "concatenate", "copy",
                    "dynamic_slice", "gather"})
# of these only the first operand is moved; the others are indices
_MOVES_FIRST = frozenset({"dynamic_slice", "gather"})
# primitives whose one sub-program takes the operands as they are
_CALLS = frozenset({"jit", "closed_call", "remat2", "custom_vjp_call",
                    "custom_jvp_call"})
_NARROW = "narrow"
_OUT = "returned"

# network -> its plan: a memo of a function of the network alone, so that
# each program that syncs traces the forward's plan once a process, not once
# a trace
_plans: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _inside(eqn, i: int):
    """[(jaxpr, invar)]: what operand ``i`` of ``eqn`` is inside its
    sub-programs, or None where it is not handed in as it is (a scan's
    carry, a branch index, any other primitive)."""
    name, p = eqn.primitive.name, eqn.params
    if name == "cond":
        if i == 0:
            return None
        return [(b.jaxpr, b.jaxpr.invars[i - 1]) for b in p["branches"]]
    if name == "scan":
        if p["num_consts"] <= i < p["num_consts"] + p["num_carry"]:
            return None
        body = p["jaxpr"].jaxpr
        return [(body, body.invars[i])]
    if name in _CALLS:
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = getattr(p.get(key), "jaxpr", p.get(key))
            if sub is not None and len(sub.invars) == len(eqn.invars):
                return [(sub, sub.invars[i])]
    return None


def _uses(jaxpr, var, dtype) -> set:
    """What ``jaxpr`` does with ``var``: ``"narrow"`` for a use that
    converts it to ``dtype`` (and hands the result to no convolution), the
    primitive's name for any other use, ``"returned"`` where it leaves the
    program; followed through moves and into sub-programs. Empty: unused."""
    found = {_OUT} if any(v is var for v in jaxpr.outvars) else set()
    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if v is not var:
                continue
            name = eqn.primitive.name
            if name == "convert_element_type":
                if np.dtype(eqn.params["new_dtype"]) != dtype:
                    found.add(name)
                elif "conv_general_dilated" in _uses(jaxpr, eqn.outvars[0],
                                                     dtype):
                    found.add("conv_general_dilated")
                else:
                    found.add(_NARROW)
            elif name in _MOVES and (i == 0 or name not in _MOVES_FIRST):
                found |= _uses(jaxpr, eqn.outvars[0], dtype)
            else:
                inner = _inside(eqn, i)
                if inner is None:
                    found.add(name)
                for sub, sub_var in inner or ():
                    found |= _uses(sub, sub_var, dtype)
    return found


def target_plan(net, params) -> Optional[Any]:
    """The dtype of each target leaf, a tree like ``params`` (which gives
    the leaves' shapes), or None where the target is held as the parameters
    are: a float32 compute dtype, or double-Q off (nothing reads the
    target). Made once a network, from ``jax.make_jaxpr`` of
    ``net.apply_learner`` at the smallest input it takes (one sequence, one
    step) and cached: a leaf takes the compute dtype where every use of it
    converts it to that dtype (``_uses``), and keeps its own otherwise."""
    dtype = np.dtype(net.module.compute_dtype)
    if dtype == np.float32 or not net.config.use_double:
        return None
    if net in _plans:
        return _plans[net]
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    h, w, s = net.obs_hw

    def target_forward(p, obs, la, hidden):
        return net.apply_learner(p, obs, la, hidden)[0]

    closed = jax.make_jaxpr(target_forward)(
        shapes, jax.ShapeDtypeStruct((1, 1, h, w, s), dtype),
        jax.ShapeDtypeStruct((1, 1, net.action_dim), jnp.float32),
        jax.ShapeDtypeStruct((1, 2, net.state_half), jnp.float32))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    plan = treedef.unflatten([
        dtype if (leaf.dtype == np.float32
                  and _uses(closed.jaxpr, var, dtype) == {_NARROW})
        else np.dtype(leaf.dtype)
        for leaf, var in zip(leaves, closed.jaxpr.invars)])
    _plans[net] = plan
    return plan


def target_of(net, params):
    """``params`` as the target holds them: each leaf in the dtype
    ``target_plan`` gives it (the identity where there is no plan). Every
    place that makes a target goes through it: ``create_train_state``, the
    sync, a checkpoint's restore (through the train state's own target)."""
    plan = target_plan(net, params)
    if plan is None:
        return params
    return jax.tree_util.tree_map(lambda d, p: p.astype(d), plan, params)


def like_target(target_params, tree):
    """``tree`` (host or device arrays) cast leaf by leaf to the dtypes of
    ``target_params``, a target ``target_of`` made: how a checkpoint's
    target, or the parameters a warm start copies into it, enter the train
    state whatever dtype they were saved in. Always a copy."""
    return jax.tree_util.tree_map(
        lambda t, x: np.array(x, dtype=np.dtype(t.dtype)), target_params,
        tree)


def target_block(target_params) -> Dict[str, int]:
    """The record's one-shot ``target`` block: the target's leaves held
    narrower than float32 and those kept, its bytes, and what the same tree
    would hold in float32."""
    leaves = jax.tree_util.tree_leaves(target_params)
    narrowed = sum(np.dtype(x.dtype).itemsize < 4
                   and jnp.issubdtype(x.dtype, jnp.floating) for x in leaves)
    return {"leaves_narrowed": int(narrowed),
            "leaves_kept": len(leaves) - int(narrowed),
            "bytes": int(sum(x.size * np.dtype(x.dtype).itemsize
                             for x in leaves)),
            "bytes_float32": int(sum(x.size * 4 for x in leaves))}
