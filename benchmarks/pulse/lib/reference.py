"""The plain reference: a key-value map built from the seed's data, with the
run's requests replayed against it in submission order.

It follows ``chip_smoke.expected``'s semantics -- a find returns the stored
value and found=1, or ``KEY_NOT_FOUND`` and found=0; an update of a present
key stores its value and answers found=1, of an absent key answers found=0
and stores nothing -- and imports nothing of the program.  The map is a
sorted key array (one ``searchsorted`` for every request) plus a dict of
the keys the run has updated so far.
"""

from __future__ import annotations

import numpy as np

from traffic import FIND, UPDATE

KEY_NOT_FOUND = -(2**31) + 1


class KeyValueReference:
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys)[order]
        self.values = np.asarray(values)[order]

    def lookup(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(value, found) of each key in the data as built."""
        keys = np.asarray(keys, np.int64)
        i = np.minimum(np.searchsorted(self.keys, keys), len(self.keys) - 1)
        found = self.keys[i] == keys
        value = np.where(found, self.values[i], KEY_NOT_FOUND)
        return value.astype(np.int64), found.astype(np.int64)

    def replay(self, op, key, value) -> tuple[np.ndarray, np.ndarray]:
        """Expected (value, found) of every request, in submission order;
        an update's expected value is the value it writes."""
        op = np.asarray(op)
        key = np.asarray(key, np.int64)
        base_val, base_found = self.lookup(key)
        want_val, want_found = base_val.copy(), base_found.copy()
        updated: dict[int, int] = {}
        upd_keys = set(key[op == UPDATE].tolist())
        for i in np.flatnonzero((op == UPDATE) | np.isin(key, list(upd_keys))):
            k = int(key[i])
            if op[i] == UPDATE:
                want_val[i] = int(value[i])
                if base_found[i]:
                    updated[k] = int(value[i])
            elif k in updated:
                want_val[i] = updated[k]
        return want_val, want_found


def compare(op, want_val, want_found, got_val, got_found, done) -> dict:
    """Counts of the numbers ``correct`` is decided on (each has limit 0):
    requests never served (not DONE), and answers that differ from the
    reference.  A find is judged by value and found flag, an update by its
    found flag."""
    op = np.asarray(op)
    done = np.asarray(done, bool)
    is_find = op == FIND
    wrong_find = is_find & done & (
        (np.asarray(got_val) != want_val) | (np.asarray(got_found) != want_found)
    )
    wrong_upd = (~is_find) & done & (np.asarray(got_found) != want_found)
    return {
        "not_served": int((~done).sum()),
        "wrong_answers": int((wrong_find | wrong_upd).sum()),
        "wrong_mask": wrong_find | wrong_upd | ~done,
    }
