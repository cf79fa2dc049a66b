"""Which leaves of a parameter tree the model converts at every use.

A checkpoint holds float32; the decode-mode model computes in
``compute_dtype`` and rounds a ``Dense`` kernel, bias or ``Embed`` table
to it each time it is used (Flax's ``promote_dtype``), inside every decode
step and every prefill chunk. Rounding once when the weights are set gives
the same bits and leaves the conversion, and half the bytes read, out of
the step. A norm's ``scale`` or the SGU's spatial matrices are read in
float32 and must stay so.

Two ways to say which leaf is which. ``promoted_mask`` is the rule Flax
gives — the parameters ``nn.Dense`` and ``nn.Embed`` hand to
``promote_dtype`` — and costs nothing; the engine uses it. ``cast_mask``
reads the same off the programs themselves: they are traced once,
abstractly, and a leaf is cast exactly when each of its consumers —
followed through calls and into the bodies of loops that only read it — is
a ``convert_element_type`` to the compute type; anything the walker does
not know keeps the leaf as it is. A trace of a 24-layer model takes
seconds, which an engine's start should not pay (PERF.md, PR 29), so the
trace is the reference the tests hold the rule to, for every layout the
repo ships.
"""

from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
from jax.extend.core import Literal


def _open(jaxpr):
    return getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr or Jaxpr


def _callees(eqn, i: int):
    """Where operand ``i`` of ``eqn`` lands: [(sub-jaxpr, its variable)],
    or None when the walker cannot say (then the leaf is left alone)."""
    name, p = eqn.primitive.name, eqn.params
    if name in ("jit", "pjit"):  # a call: operands one for one
        sub = _open(p["jaxpr"])
        return [(sub, sub.invars[i])]
    if name == "while":  # operands: cond's constants, body's, the carry
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        if i < nc:
            return [(_open(p["cond_jaxpr"]), _open(p["cond_jaxpr"]).invars[i])]
        if i < nc + nb:
            body = _open(p["body_jaxpr"])
            return [(body, body.invars[i - nc])]
        return None  # carried: the loop may rewrite it
    if name == "scan":  # operands: constants, the carry, the scanned
        if i < p["num_consts"]:
            body = _open(p["jaxpr"])
            return [(body, body.invars[i])]
        return None
    if name == "cond":  # operand 0 picks the branch, the rest go to each
        if i == 0:
            return None
        return [(_open(b), _open(b).invars[i - 1]) for b in p["branches"]]
    return None


def _uses(jaxpr, index: dict) -> dict:
    """variable -> [(equation, operand number)] of ``jaxpr``, built once."""
    uses = index.get(id(jaxpr))
    if uses is None:
        uses = index[id(jaxpr)] = {}
        for eqn in jaxpr.eqns:
            for i, operand in enumerate(eqn.invars):
                if not isinstance(operand, Literal):
                    uses.setdefault(operand, []).append((eqn, i))
    return uses


def _cast_at_every_use(jaxpr, var, dtype, index: dict) -> bool:
    if any(out is var for out in jaxpr.outvars):
        return False
    uses = _uses(jaxpr, index).get(var, ())
    for eqn, i in uses:
        if eqn.primitive.name == "convert_element_type":
            if eqn.params["new_dtype"] != dtype:
                return False
            continue
        inner = _callees(eqn, i)
        if inner is None or not all(
            _cast_at_every_use(sub, v, dtype, index) for sub, v in inner
        ):
            return False
    return bool(uses)


def _wider(leaf, dtype) -> bool:
    """A floating leaf that ``dtype`` would narrow (held wider than it
    is, a leaf would cost the bytes this is there to save)."""
    return (
        jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.dtype.itemsize > dtype.itemsize
    )


# what ``nn.Dense`` / ``nn.DenseGeneral`` and ``nn.Embed`` name the
# parameters they pass through ``promote_dtype`` at every call
_PROMOTED = frozenset({"kernel", "bias", "embedding"})


def promoted_mask(params, dtype) -> List[bool]:
    """One bool per leaf of ``params`` (flatten order): a leaf wider than
    ``dtype`` that Flax's own modules convert to their ``dtype`` at every
    use. A parameter a module declares itself (``self.param``) is never
    one: what its code does with it, this rule cannot know."""
    dtype = jnp.dtype(dtype)
    return [
        _wider(leaf, dtype) and getattr(path[-1], "key", None) in _PROMOTED
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    ]


def cast_mask(programs: Callable, params, dtype, *operands) -> List[bool]:
    """The same mask read off a trace: whether
    ``programs(params, *operands)`` converts a leaf to ``dtype`` at every
    one of its uses. Traced abstractly (no device work); a tree with no
    floating leaf wider than ``dtype`` is not traced at all."""
    dtype = jnp.dtype(dtype)
    wider = [_wider(leaf, dtype) for leaf in jax.tree.leaves(params)]
    if not any(wider):
        return wider
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, *operands)
    )
    jaxpr = jax.make_jaxpr(programs)(*abstract).jaxpr
    # the tree's leaves are the first inputs, in flatten order
    index: dict = {}
    return [
        w and _cast_at_every_use(jaxpr, var, dtype, index)
        for w, var in zip(wider, jaxpr.invars)
    ]


def serve_tree(params, mask: List[bool], dtype):
    """``params`` with the masked leaves held in ``dtype``; every other
    leaf is the same array, not a copy."""
    leaves, treedef = jax.tree.flatten(params)
    return treedef.unflatten([
        leaf.astype(dtype) if cast else leaf
        for leaf, cast in zip(leaves, mask)
    ])
