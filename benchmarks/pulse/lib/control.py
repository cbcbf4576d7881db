"""The control: the plain reference put in the program's place, with one of
the configuration's guarantees broken (``cfg["control"]``), so that the
comparison that decides ``correct`` is shown to fail.

  bounded_probe  (breaks "exactness") a find gives up after ``probe_nodes``
                 nodes (one quantum's worth; the depths the structure read
                 from the built arena) and answers not-found: the temptation
                 of capping a request's work

It answers each admitted request in the round it is admitted, 32 a round per
structure, like the service's slot groups; it runs no device work.
"""

from __future__ import annotations

import time

import numpy as np

from reference import KEY_NOT_FOUND, KeyValueReference
from traffic import OPS, UPDATE

SLOTS = 32


class ControlService:
    def __init__(self, cfg: dict, built: dict, struct, keys, values):
        from repro.core.iterator import STATUS_DONE

        self._done = STATUS_DONE
        ctl = cfg["control"]
        self.how = ctl["how"]
        if self.how != "bounded_probe":
            raise ValueError(f"unknown control {self.how!r}")
        self.ref = KeyValueReference(keys, values)
        self.updated: dict[int, int] = {}
        deep = np.asarray(built["depths"]) > int(ctl["probe_nodes"])
        self.too_deep = set(np.asarray(keys)[deep].tolist())
        self.op_of = {spec: OPS.index(op) for op, spec in built["op_spec"].items()}
        self.answer_cols = {built["op_spec"][op]: struct.ANSWER[op] for op in built["op_spec"]}
        self.pending: list = []

    def submit(self, r) -> None:
        self.pending.append(r)

    def _answer(self, r) -> tuple[int, int]:
        val, found = self.ref.lookup(np.asarray([r.query]))
        val, found = int(val[0]), int(found[0])
        if self.op_of[r.structure] == UPDATE:
            if found:
                self.updated[r.query] = r.value
            return r.value, found
        if r.query in self.too_deep:
            return KEY_NOT_FOUND, 0
        if r.query in self.updated:
            val = self.updated[r.query]
        return val, found

    def step(self) -> None:
        taken: dict[str, int] = {}
        keep = []
        now = time.perf_counter()
        for r in self.pending:
            if taken.get(r.structure, 0) >= SLOTS:
                keep.append(r)
                continue
            taken[r.structure] = taken.get(r.structure, 0) + 1
            val, found = self._answer(r)
            vcol, fcol = self.answer_cols[r.structure]
            res = np.zeros(max(c for c in (vcol, fcol) if c is not None) + 1, np.int32)
            res[0] = r.query
            if vcol is not None:
                res[vcol] = val
            res[fcol] = found
            r.result, r.status, r.finish_s = res, self._done, now
        self.pending = keep

