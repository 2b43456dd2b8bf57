"""Shared collective helpers."""

from __future__ import annotations

import jax


def manual_axes() -> frozenset:
    """The current trace's ``shard_map`` manual mesh axes (empty outside
    one)."""
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def vma_tracked(axis_name) -> bool:
    """True inside a ``shard_map(check_vma=True)`` region over
    ``axis_name``.  Under ``check_vma=False`` every value types as
    invariant and ``pcast``'s transpose (a checked psum) raises, so the
    helpers below must know which world they are in; ``axis_index`` is
    device-varying by definition, which makes it the probe."""
    if axis_name not in manual_axes():
        return False
    return axis_name in jax.typeof(jax.lax.axis_index(axis_name)).vma


def ensure_varying(x, axis_name):
    """Idempotently mark ``x`` device-varying over ``axis_name``.

    Collectives require varying (vma-tracked) inputs inside
    ``shard_map``; ``pcast`` raises when the value is already varying, so
    this is the safe form for values of unknown provenance.  Pytree-aware.
    A no-op where vma is not tracked (every value is implicitly varying).
    """
    if not vma_tracked(axis_name):
        return x
    return jax.tree_util.tree_map(
        lambda v: v if axis_name in jax.typeof(v).vma
        else jax.lax.pcast(v, axis_name, to="varying"), x)


def vary_like(p, x):
    """``p`` cast device-varying over every manual axis ``x`` varies
    over.  For parameters entering a ``custom_vjp`` next to an
    activation: under ``check_vma=True`` the bwd rule's cotangent must
    carry the primal's type, and a replicated parameter's cotangent
    varies like the activation — the cast's transpose (a psum) is what
    reduces it.  No-op without vma tracking."""
    missing = tuple(jax.typeof(x).vma - jax.typeof(p).vma)
    return jax.lax.pcast(p, missing, to="varying") if missing else p


def varying_test(axis_name):
    """Predicate ``v -> bool``: is ``v`` device-varying over
    ``axis_name``?  vma only exists under ``shard_map(check_vma=True)``;
    for a vmap/pmap axis, outside any trace, or under ``check_vma=False``
    the notion doesn't apply, so everything reports True and callers fall
    through to the normal collective.  Probes the tracking once, for
    whole-tree callers."""
    if not vma_tracked(axis_name):
        return lambda v: True
    return lambda v: axis_name in jax.typeof(v).vma


def psum_if_varying(tree, axis_name, strict: bool = False):
    """``psum`` only the leaves that are actually device-varying.

    An *invariant* leaf inside ``shard_map`` holds the same value on every
    device — for gradients that means it was already cross-device reduced
    (JAX auto-psums grads of replicated inputs), and psumming it again
    would multiply by axis size.  Such leaves pass through unchanged,
    treated as ALREADY-SUMMED: callers that average afterwards still divide
    them by axis size.  Pass a value that is replicated-but-not-a-sum and
    that division is wrong — these helpers are for gradients only.

    ``strict=True`` makes that contract loud: any invariant leaf raises
    instead of silently passing through, for callers who expect every leaf
    to be a locally-computed (varying) gradient.
    """
    varying = varying_test(axis_name)

    def one(path, v):
        if varying(v):
            return jax.lax.psum(v, axis_name)
        if strict:
            raise ValueError(
                f"psum_if_varying(strict=True): leaf {jax.tree_util.keystr(path)} "
                f"is device-invariant over axis {axis_name!r}; it would be "
                "passed through as an already-summed gradient. If this leaf "
                "is not a gradient, do not route it through this helper.")
        return v
    return jax.tree_util.tree_map_with_path(one, tree)


def sds_like(shape, dtype, like):
    """ShapeDtypeStruct for a ``pallas_call`` output, vma-aware.

    Inside ``shard_map`` (manual mesh axes) JAX 0.9 requires the output's
    varying-axes set; inherit it from a representative input so kernels
    work standalone AND inside explicit-collective regions.  Under a
    ``vmap``/``scan`` trace inside the region the batched aval can lose
    its vma — fall back to "varying over every manual axis", the only
    sound upper bound there.
    """
    ma = manual_axes()
    if not ma:
        return jax.ShapeDtypeStruct(shape, dtype)
    vma = getattr(jax.typeof(like), "vma", None)
    if vma is None:
        vma = frozenset(ma)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
