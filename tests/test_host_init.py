"""The service's compiled ``init`` (``traversal_service._SlotGroup``): for
every structure's iterator, one jitted program over a padded batch, its
fixed arguments committed to the host CPU device, gives bit-for-bit the
``(ptr0, scratch0)`` of the eager ``iterator.init``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arena import NULL, ArenaBuilder
from repro.core.structures import bst, btree, hash_table, linked_list, skiplist
from repro.serving.traversal_service import StructureSpec, _SlotGroup

SLOTS, N_FRESH, N_BUCKETS = 16, 11, 64
KEYS = np.arange(100, 148, 4, dtype=np.int32)  # 12 keys: most of 64 buckets empty


def _heap():
    """One arena holding every structure; returns each one's fixed args."""
    b = ArenaBuilder(512, btree.NODE_WORDS)
    head = linked_list.build_into(b, KEYS, KEYS * 3)
    root, _ = btree.build_into(b, KEYS, KEYS * 5)
    broot, _ = bst.build_into(b, KEYS, KEYS * 7)
    shead = skiplist.build_into(b, KEYS, KEYS * 11)
    heads = hash_table.build_into(b, KEYS, KEYS * 13, N_BUCKETS)
    sent = hash_table.build_writable(b, KEYS + 1000, KEYS, N_BUCKETS)
    return {"list": (head,), "btree": (root,), "bst": (broot,), "skip": (shead,),
            "hash": (jnp.asarray(heads),), "sent": (sent,)}


def _padded(vals):
    out = np.zeros(SLOTS, np.int32)  # the service pads with zeros
    out[: len(vals)] = vals
    return out


# present keys, absent keys between them and past both ends, a negative key
QUERIES = _padded([100, 104, 144, 101, 99, 150, 7, -5, 120, 2**31 - 1, 133])
VALUES = _padded(np.arange(N_FRESH) * 17 + 3)
OPS = _padded(np.arange(N_FRESH) % 3)  # find, insert, delete

CASES = {
    "hash_find": (lambda: hash_table.find_iterator(N_BUCKETS), "hash", (QUERIES,)),
    "hash_rw": (lambda: hash_table.rw_iterator(N_BUCKETS), "sent", (OPS, QUERIES, VALUES)),
    "hash_insert": (lambda: hash_table.insert_iterator(N_BUCKETS), "sent", (QUERIES, VALUES)),
    "hash_delete": (lambda: hash_table.delete_iterator(N_BUCKETS), "sent", (QUERIES,)),
    "btree_find": (btree.find_iterator, "btree", (QUERIES,)),
    "btree_range": (btree.range_aggregate_iterator, "btree", (QUERIES, QUERIES + 20)),
    "btree_update": (btree.update_iterator, "btree", (QUERIES, VALUES)),
    "bst_find": (bst.find_iterator, "bst", (QUERIES,)),
    "bst_update": (bst.update_iterator, "bst", (QUERIES, VALUES)),
    "skiplist_find": (skiplist.find_iterator, "skip", (QUERIES,)),
    "skiplist_insert": (skiplist.insert_iterator, "skip", (QUERIES, VALUES)),
    "skiplist_delete": (skiplist.delete_iterator, "skip", (QUERIES,)),
    "list_find": (linked_list.find_iterator, "list", (QUERIES,)),
    "list_rw": (linked_list.rw_iterator, "list", (OPS, QUERIES, VALUES)),
    "list_insert": (linked_list.insert_iterator, "list", (QUERIES, VALUES)),
    "list_delete": (linked_list.delete_iterator, "list", (QUERIES,)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_host_init_matches_eager_init(case):
    make, struct, batch = CASES[case]
    it = make()
    init_args = _heap()[struct]
    cpu = jax.devices("cpu")[0]
    g = _SlotGroup(case, StructureSpec(it, init_args), SLOTS, cpu)
    assert all(a.devices() == {cpu} for a in jax.tree.leaves(g.init_args))
    ptr, scr = g.init(*jax.device_put(batch, cpu), *g.init_args)
    assert ptr.devices() == {cpu} and scr.devices() == {cpu}

    want_ptr, want_scr = it.init(*(jnp.asarray(a) for a in batch), *init_args)
    ptr, scr = jax.device_get((ptr, scr))
    assert ptr.dtype == scr.dtype == np.int32
    assert ptr.shape == (SLOTS,) and scr.shape == (SLOTS, it.scratch_words)
    np.testing.assert_array_equal(ptr, np.asarray(want_ptr))
    np.testing.assert_array_equal(scr, np.asarray(want_scr))
    if case == "hash_find":  # the batch reaches empty buckets and real chains
        assert (ptr == NULL).any() and (ptr != NULL).any()
