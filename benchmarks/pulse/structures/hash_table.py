"""The WebService hash index: ``repro.core.structures.hash_table`` built from
the seed's data through the program's own ``ArenaBuilder`` and
``build_into`` (4-word nodes ``[key, value, next, pad]``, push-front chains).
Serves finds; the mix may hold no updates."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.arena import NULL, ArenaBuilder
from repro.core.structures import hash_table
from repro.serving.traversal_service import StructureSpec

# answer columns of the result scratch pad: (value, found) per operation
ANSWER = {"find": (1, 2)}


def chain_depths(rows: np.ndarray, heads: np.ndarray, keys) -> np.ndarray:
    """Iterations a find of each of ``keys`` takes: its place in its bucket's
    chain plus one, read by walking every chain of the built arena from its
    head (``rows``: the arena's node records)."""
    depth_of_row = np.zeros(len(rows), np.int64)
    ptr = heads[heads != NULL]
    d = 1
    while len(ptr):
        depth_of_row[ptr] = d
        ptr = rows[ptr, hash_table.NEXT]
        ptr = ptr[ptr != NULL]
        d += 1
    linked = np.flatnonzero(depth_of_row)
    stored = rows[linked, hash_table.KEY]
    order = np.argsort(stored)
    at = np.searchsorted(stored[order], keys)
    at = np.minimum(at, len(order) - 1)
    row = linked[order[at]]
    if not np.array_equal(rows[row, hash_table.KEY], np.asarray(keys, np.int32)):
        raise ValueError("a key is in no bucket chain of the built arena")
    return depth_of_row[row]


def build(cfg: dict, keys, values, num_shards: int) -> dict:
    if cfg["node_words"] != hash_table.NODE_WORDS:
        raise ValueError(
            f"config node_words {cfg['node_words']} != the hash table's "
            f"{hash_table.NODE_WORDS}"
        )
    n_buckets = int(cfg["n_buckets"])
    cap = -(-len(keys) // (8 * num_shards)) * 8 * num_shards
    b = ArenaBuilder(cap, hash_table.NODE_WORDS, num_shards=num_shards)
    heads = hash_table.build_into(b, keys, values, n_buckets)
    depths = chain_depths(b.data, heads, keys)
    spec = StructureSpec(hash_table.find_iterator(n_buckets), (jnp.asarray(heads),))
    return {
        "arena": b.finish(),
        "specs": {"hash": spec},
        "op_spec": {"find": "hash"},
        "depths": depths,
        "info": {"buckets": n_buckets, "keys_per_bucket": len(keys) / n_buckets},
    }
