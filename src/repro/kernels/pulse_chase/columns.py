"""Lane-columnar form of a per-lane iterator body, for the Mosaic compiler.

``jax.vmap`` of an iterator's per-lane ``(node, ptr, scratch)`` function
produces batched ``scatter``, ``gather`` and ``argmax`` ops, and scalars
batched into 1-D vectors; the TPU kernel compiler (Mosaic) lowers none of
these.  This module evaluates the per-lane jaxpr directly over a wave of
lanes instead, keeping every value two-dimensional -- lanes along sublanes,
words along lanes:

  * a per-lane scalar is a ``(G, 1)`` column, a per-lane ``(n,)`` vector a
    ``(G, n)`` tile (``G == 1`` for values that do not depend on the lane);
  * a write to a word (``x.at[i].set(v)``, ``scatter``) is a select against
    a lane iota; a read at a traced index (``dynamic_slice``) is
    a one-hot select followed by a lane reduction; ``argmax`` is a max
    reduction followed by a min over the matching lane indices.

Elementwise primitives are re-bound on operands broadcast to one shape, so
the arithmetic -- and therefore every result bit -- is the per-lane
function's own.  Per-lane values of rank 2 or more are not supported and
raise ``NotImplementedError`` at trace time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core as jcore

_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs", "and", "or",
    "xor", "not", "min", "max", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "clamp", "convert_element_type", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "integer_pow",
    "population_count",
}
_REDUCE = {
    "reduce_max": jnp.max,
    "reduce_min": jnp.min,
    "reduce_sum": jnp.sum,
    "reduce_or": jnp.max,
}
_SCATTER = {
    "scatter": lambda old, upd: upd,
    "scatter-add": lambda old, upd: old + upd,
    "scatter-max": jnp.maximum,
    "scatter-min": jnp.minimum,
}


def _lane_iota(n: int) -> jax.Array:
    return lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _pick(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-lane ``x[idx]`` for ``x`` (R, n), ``idx`` (R, 1): one-hot + sum."""
    dt = x.dtype
    xi = x.astype(jnp.int32) if dt == jnp.bool_ else x
    hit = _lane_iota(x.shape[1]) == idx
    out = jnp.sum(jnp.where(hit, xi, jnp.zeros_like(xi)), axis=1, keepdims=True)
    return out.astype(dt)


def _rank(v) -> int:
    rank = len(v.aval.shape)
    if rank > 1:
        raise NotImplementedError(
            f"lane-columnar form supports per-lane rank <= 1, got {v.aval}"
        )
    return rank


def _read(env, v):
    if isinstance(v, jcore.Literal):
        return jnp.full((1, 1), v.val, v.aval.dtype)
    return env[v]


def _eval(jaxpr, consts, args):
    env = {}
    for v, c in zip(jaxpr.constvars, consts):
        _rank(v)
        c = jnp.asarray(c, v.aval.dtype)
        env[v] = c.reshape(1, -1) if c.ndim else c.reshape(1, 1)
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for eqn in jaxpr.eqns:
        ins = [_read(env, v) for v in eqn.invars]
        for v in (*eqn.invars, *eqn.outvars):
            _rank(v)
        outs = _apply(eqn, ins)
        for v, o in zip(eqn.outvars, outs):
            env[v] = o
    return [_read(env, v) for v in jaxpr.outvars]


def _apply(eqn, ins):
    name = eqn.primitive.name
    p = eqn.params
    in_shapes = [v.aval.shape for v in eqn.invars]
    out_aval = eqn.outvars[0].aval
    if name == "jit":  # nested jit (jnp.where and friends): inline it
        sub = p["jaxpr"]
        return _eval(sub.jaxpr, sub.consts, ins)
    if name == "squeeze":  # (1,) -> (): both are (G, 1) columns
        return [ins[0]]
    if name == "broadcast_in_dim":
        x = ins[0]
        n = p["shape"][0] if p["shape"] else 1
        return [jnp.broadcast_to(x, (x.shape[0], n))]
    if name == "iota":
        return [_lane_iota(p["shape"][0]).astype(p["dtype"])]
    if name == "slice":
        (x,) = ins
        (s,), (e,) = p["start_indices"], p["limit_indices"]
        st = (p["strides"] or (1,))[0]
        return [lax.slice(x, (0, s), (x.shape[0], e), (1, st))]
    if name == "argmax":
        x = ins[0]
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        best = jnp.max(x, axis=1, keepdims=True)
        n = x.shape[1]
        idx = jnp.where(x == best, _lane_iota(n), jnp.int32(n))
        return [jnp.min(idx, axis=1, keepdims=True).astype(p["index_dtype"])]
    if name in _REDUCE:
        x = ins[0]
        dt = x.dtype
        xi = x.astype(jnp.int32) if dt == jnp.bool_ else x
        return [_REDUCE[name](xi, axis=1, keepdims=True).astype(dt)]
    if name == "dynamic_slice":
        x, idx = ins[0], ins[1]
        n, k = in_shapes[0][0], p["slice_sizes"][0]
        if k == n:
            return [x]
        start = jnp.clip(idx.astype(jnp.int32), 0, n - k)
        out = _pick(x, start)
        for j in range(1, k):
            col = _pick(x, start + j)
            out = jnp.where(_lane_iota(k) == j, col, out)
        return [out]
    if name in _SCATTER:
        dn = p["dimension_numbers"]
        if tuple(dn.scatter_dims_to_operand_dims) != (0,):
            raise NotImplementedError(f"lane-columnar scatter {dn}")
        x, idx, upd = ins
        n = in_shapes[0][0]
        idx = idx.astype(jnp.int32)
        if "CLIP" in str(p["mode"]):
            idx = jnp.clip(idx, 0, n - 1)
        hit = _lane_iota(n) == idx  # out-of-range indices drop the update
        return [jnp.where(hit, _SCATTER[name](x, upd), x).astype(x.dtype)]
    if name == "rev":
        return [lax.rev(ins[0], (1,))]
    if name == "concatenate":
        return [jnp.concatenate(
            [jnp.broadcast_to(x, (max(i.shape[0] for i in ins), x.shape[1])) for x in ins],
            axis=1,
        )]
    if name not in _ELEMENTWISE:
        raise NotImplementedError(f"lane-columnar form of {name!r}")
    # elementwise: broadcast every operand to one (R, n) shape and re-bind
    rows = max(x.shape[0] for x in ins)
    cols = out_aval.shape[0] if out_aval.shape else 1
    args = [jnp.broadcast_to(x, (rows, cols)) for x in ins]
    out = eqn.primitive.bind(*args, **p)
    return out if eqn.primitive.multiple_results else [out]


def lane_columns(step):
    """Wrap a per-lane ``step(node (W,), ptr (), scratch (S,)) -> (done, ptr,
    scratch)`` as ``f(nodes (G, W), ptr (G, 1), scratch (G, S)) -> (done
    (G, 1) bool, ptr (G, 1) int32, scratch (G, S) int32)``.  The per-lane
    jaxpr is traced once per (W, S)."""
    traced: dict = {}

    def columns(nodes, ptr, scratch):
        G, W = nodes.shape
        S = scratch.shape[1]
        closed = traced.get((W, S))
        if closed is None:
            closed = traced[(W, S)] = jax.make_jaxpr(step)(
                jax.ShapeDtypeStruct((W,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
            )
        done, nptr, nscr = _eval(closed.jaxpr, closed.consts, [nodes, ptr, scratch])
        return (
            jnp.broadcast_to(done, (G, 1)).astype(jnp.bool_),
            jnp.broadcast_to(nptr, (G, 1)).astype(jnp.int32),
            jnp.broadcast_to(nscr, (G, S)).astype(jnp.int32),
        )

    return columns
