"""Logical sharding rules: param-path pattern -> PartitionSpec, plus the
versioned shard-of-slot owner function for elastic arenas.

Conventions (Megatron TP + FSDP hybrid):
  * ``model`` axis: TP for attention heads / MLP hidden, EP for experts,
    vocab-parallel for embed/unembed.
  * ``data`` (+``pod``): FSDP shards the *other* matrix dimension, so every
    large matrix is 2-D sharded; DP handles batch.
  * Norm scales / biases / small vectors: replicated.
  * Scan-stacked params carry a leading layer axis: specs get None prepended
    automatically (detected by leaf rank vs rule rank).

The arena side (``VersionedOwnerMap``) is index translation only: arena
pointers are global row addresses, so a reshard never rewrites a pointer --
it installs a new *owner-function epoch* (a finer/coarser range partition)
and anything still carrying a shard index minted under an older epoch
(parked requests, backoff timers, dead masks) is forwarded to the shards
covering the same address range.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def mem_mesh(num_shards: int | None = None, *, devices=None, axis_name: str = "mem") -> Mesh:
    """The 1-D memory-node mesh the sharded arena is range-partitioned over.

    Built from the first ``num_shards`` of ``devices`` (default: all of
    ``jax.devices()``) with an ``Auto`` axis: the routing supersteps rely on
    plain gathers and ``with_sharding_constraint`` over arena-sized arrays,
    which an ``Explicit`` axis (``jax.make_mesh``'s default) rejects.
    """
    devs = list(jax.devices() if devices is None else devices)
    n = len(devs) if num_shards is None else num_shards
    if n > len(devs):
        raise ValueError(f"a {n}-shard mesh needs {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis_name,), axis_types=(AxisType.Auto,))


def fsdp_axes(mesh: Mesh):
    """The data-parallel axes usable for FSDP sharding."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_hint(x, mesh, *axes):
    """Best-effort ``with_sharding_constraint``.

    ``axes`` entries: mesh axis name(s), ``None``, or the placeholder
    ``"dp"`` (resolves to the (pod, data) axes present).  Axes that are
    missing from the mesh or do not divide the dim are dropped; with no mesh
    this is a no-op -- so model code can sprinkle hints freely and CPU tests
    stay mesh-free.  These hints are what keep activations batch-sharded
    through gathers (XLA loses the batch sharding at the embedding lookup;
    measured 16x replicated compute without them -- EXPERIMENTS.md S Perf).
    """
    if mesh is None or x.ndim != len(axes):
        return x
    resolved = []
    for dim, ax in zip(x.shape, axes):
        if ax == "dp":
            ax = fsdp_axes(mesh) or None
        if ax is None:
            resolved.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        if not all(a in mesh.axis_names for a in names):
            resolved.append(None)
            continue
        size = 1
        for a in names:
            size *= mesh.shape[a]
        resolved.append(ax if (dim % size == 0 and dim >= size) else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*resolved)))


def param_rules(mesh: Mesh):
    fsdp = fsdp_axes(mesh)
    fs = fsdp if fsdp else None
    return [
        # embeddings: vocab-parallel x fsdp
        (r"embed$", P("model", fs)),
        (r"unembed/w$", P(fs, "model")),
        (r"patch_proj/w$", P(fs, "model")),
        (r"frame_proj/w$", P(fs, "model")),
        # attention
        (r"(attn|self_attn|cross_attn)/wq/w$", P(fs, "model")),
        (r"(attn|self_attn|cross_attn)/wk/w$", P(fs, "model")),
        (r"(attn|self_attn|cross_attn)/wv/w$", P(fs, "model")),
        (r"(attn|self_attn|cross_attn)/wo/w$", P("model", fs)),
        (r"(attn|self_attn|cross_attn)/w[qkv]/b$", P("model")),
        (r"(attn|self_attn|cross_attn)/wo/b$", P()),
        # dense mlp
        (r"mlp/wi/w$", P(fs, "model")),
        (r"mlp/wg/w$", P(fs, "model")),
        (r"mlp/wo/w$", P("model", fs)),
        (r"mlp/wi/b$", P("model")),
        (r"mlp/wo/b$", P()),
        # moe: experts over model (EP), dims over fsdp
        (r"moe/wi$", P("model", fs, None)),
        (r"moe/wg$", P("model", fs, None)),
        (r"moe/wo$", P("model", None, fs)),
        (r"moe/router/w$", P(fs, None)),
        (r"moe/shared/wi/w$", P(fs, "model")),
        (r"moe/shared/wg/w$", P(fs, "model")),
        (r"moe/shared/wo/w$", P("model", fs)),
        # ssm
        (r"ssm/in_proj/w$", P(fs, "model")),
        (r"ssm/out_proj/w$", P("model", fs)),
        (r"ssm/conv_w$", P(None, "model")),
        (r"ssm/conv_b$", P("model")),
        (r"ssm/(A_log|dt_bias|D_skip)$", P()),
        (r"ssm/norm/scale$", P("model")),
        # everything else (norms, small vectors): replicated
        (r".*", P()),
    ]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for(path_str: str, leaf, rules) -> P:
    for pat, spec in rules:
        if re.search(pat, path_str):
            # scan-stacked leaves have extra leading axes: left-pad with None
            pad = leaf.ndim - len(spec)
            if pad < 0:
                # leaf smaller than rule (e.g. non-parametric norm) -> replicate
                return P()
            flat = (None,) * pad + tuple(spec)
            # avoid sharding tiny dims: drop axes that don't divide
            return P(*flat)
    return P()


def param_specs(params, mesh: Mesh):
    """Pytree of PartitionSpec for a param pytree."""
    rules = param_rules(mesh)

    def one(path, leaf):
        spec = spec_for(_path_str(path), leaf, rules)
        # validity: every named axis must divide the dim; else drop that axis
        fixed = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            fixed.append(ax if dim % size == 0 and dim >= size else None)
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh)
    )


def opt_state_specs(opt_state, param_spec_tree):
    """Optimizer moments mirror their param's spec; scalars replicate.

    Works for adamw {mu, nu, step} and adafactor {v: {v|vr,vc}, step}.
    """

    def like(sub):
        return jax.tree.map(lambda s: s, param_spec_tree)

    out = {}
    for k, v in opt_state.items():
        if k == "step":
            out[k] = P()
        elif k in ("mu", "nu"):
            out[k] = param_spec_tree
        elif k == "v":
            # adafactor: factored stats drop the last (vr) or second-to-last
            # (vc) axis of the param spec
            def fac(path, leaf):
                # best-effort: replicate factored stats (they are small)
                return P()

            out[k] = jax.tree_util.tree_map_with_path(fac, v)
        else:
            out[k] = jax.tree.map(lambda _: P(), v)
    return out


def batch_specs(batch, mesh: Mesh):
    """Batch dim over (pod, data); everything else replicated."""
    dp = fsdp_axes(mesh)
    dp = dp if dp else None

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        return P(dp, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(one, batch)


# ---------------------------------------------------------------------------
# Versioned shard-of-slot owner function (elastic arenas)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OwnerEpoch:
    """One version of the shard-of-slot owner function: the switch's
    translation base table (range-partition bounds) at a reshard epoch."""

    epoch: int
    bounds: tuple[int, ...]  # (num_shards + 1,) sorted row-range partition

    @property
    def num_shards(self) -> int:
        return len(self.bounds) - 1

    def owner_of(self, ptr):
        """Owning shard for global address(es); -1 when out of range."""
        b = np.asarray(self.bounds, np.int64)
        p = np.asarray(ptr, np.int64)
        shard = np.searchsorted(b, p, side="right") - 1
        valid = (p >= 0) & (p < b[-1]) & (shard >= 0) & (shard < self.num_shards)
        return np.where(valid, shard, -1).astype(np.int32)


class VersionedOwnerMap:
    """Owner-function epochs with forwarding between them.

    A reshard installs a new epoch via ``advance``.  Stale per-shard state
    minted under an older epoch is translated with ``forward_shard`` /
    ``forward_mask``: old shard -> the new shards covering the same address
    range.  Pure index translation -- pointers are global, so no record is
    ever rewritten.
    """

    def __init__(self, bounds):
        self._epochs = [OwnerEpoch(0, tuple(int(b) for b in bounds))]

    @property
    def current(self) -> OwnerEpoch:
        return self._epochs[-1]

    @property
    def epoch(self) -> int:
        return self._epochs[-1].epoch

    def at(self, epoch: int) -> OwnerEpoch:
        for e in self._epochs:
            if e.epoch == epoch:
                return e
        raise KeyError(f"unknown owner epoch {epoch}")

    def advance(self, bounds) -> OwnerEpoch:
        """Install a new owner function (the forwarding epoch boundary)."""
        new = tuple(int(b) for b in bounds)
        cur = self.current
        if new[0] != cur.bounds[0] or new[-1] != cur.bounds[-1]:
            raise ValueError(
                "an owner epoch must cover the same address space: "
                f"{cur.bounds[0]}..{cur.bounds[-1]} vs {new[0]}..{new[-1]}"
            )
        nxt = OwnerEpoch(cur.epoch + 1, new)
        self._epochs.append(nxt)
        return nxt

    def forward_shard(
        self, shard: int, *, from_epoch: int, to_epoch: int | None = None
    ) -> tuple[int, ...]:
        """New-epoch shards whose ranges overlap old ``shard``'s range."""
        src = self.at(from_epoch)
        dst = self.current if to_epoch is None else self.at(to_epoch)
        if not 0 <= shard < src.num_shards:
            raise ValueError(f"shard {shard} out of range for epoch {from_epoch}")
        lo, hi = src.bounds[shard], src.bounds[shard + 1]
        db = np.asarray(dst.bounds, np.int64)
        first = int(np.searchsorted(db, lo, side="right")) - 1
        last = int(np.searchsorted(db, hi, side="left"))
        return tuple(range(max(first, 0), min(last, dst.num_shards)))

    def forward_mask(
        self, mask, *, from_epoch: int, to_epoch: int | None = None
    ) -> np.ndarray:
        """Forward a per-shard bool mask (e.g. suspected-dead): a new shard
        is set iff any overlapping old shard was set."""
        src = self.at(from_epoch)
        dst = self.current if to_epoch is None else self.at(to_epoch)
        mask = np.asarray(mask, bool)
        if mask.shape != (src.num_shards,):
            raise ValueError(
                f"mask shape {mask.shape} != ({src.num_shards},) of epoch "
                f"{from_epoch}"
            )
        out = np.zeros(dst.num_shards, bool)
        for s in np.flatnonzero(mask):
            for d in self.forward_shard(
                int(s), from_epoch=from_epoch, to_epoch=dst.epoch
            ):
                out[d] = True
        return out
