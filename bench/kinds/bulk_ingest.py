"""``bulk_ingest``: closed-loop ``add_many`` of dense blocks into a
``SketchIndex`` that starts empty, checked entry by entry against the
reference's sketches and layout (``bench.reference``).

Traffic keys: ``block`` (columns per call), ``pool`` (blocks generated).
Configuration keys: ``index``, ``data`` (its generator's
``corpus_block``).
"""
from __future__ import annotations

import numpy as np

from bench import drive, reference
from bench.data import generator


class BulkIngest:
    """Closed-loop bulk ``add_many`` into an index that starts empty: the
    loader waits for each call.  A pool of ``pool`` dense blocks of
    ``block`` columns is generated in set-up from the seed and cycled under
    fresh names."""

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.gen = generator(self.config["data"]["generator"])

    def setup(self, seconds: float, *, program: bool = True) -> None:
        """Generate the pool of blocks and warm up ``add_many`` on a
        throwaway index (``program=False``: the blocks only)."""
        rows = self.traffic["block"]
        self.blocks = [self.gen.corpus_block(self.config["data"], self.seed,
                                             b, rows)
                       for b in range(self.traffic["pool"])]
        if not program:
            return
        warm = drive.sketch_index(self.config)
        warm.add_many([f"w{i}" for i in range(rows)], self.blocks[0].dense)
        del warm
        self.index = drive.sketch_index(self.config)

    def window(self, seconds: float, ann) -> drive.Window:
        rows = self.traffic["block"]
        calls, failed, reqs, errors = 0, 0, [], []
        self.acked = []           # (call, pool block) of each stored call
        t0 = drive.clock()
        with ann("bench.window"):
            while drive.clock() - t0 < seconds:
                p = calls % len(self.blocks)
                s = drive.clock()
                try:
                    with ann("bench.add_many"):
                        self.index.add_many(
                            [f"i{calls}_{c}" for c in range(rows)],
                            self.blocks[p].dense)
                    self.acked.append((calls, p))
                except Exception as e:
                    failed += 1
                    errors.append(repr(e))
                reqs.append({"start": s - t0, "end": drive.clock() - t0})
                calls += 1
        span = drive.clock() - t0
        return drive.Window(
            seconds=span, attempted=calls, failed=failed,
            e2e={"ingest_cols_per_s": len(self.acked) * rows / span},
            requests=reqs,
            facts={"block_rows": rows,
                   "universe": self.config["data"]["universe"],
                   "errors": errors[:3]})

    def reference_layouts(self, precision: str = "float32") -> list:
        kw = drive.layout_kw(self.config)
        return [reference.sketch_columns(b.indptr, b.keys, b.vals,
                                         precision=precision, **kw)
                for b in self.blocks]

    def compare(self, stored, refs) -> dict:
        """``entries_differ``: the share of stored entries, over every
        acknowledged column, that are not the reference's (an id, its value,
        or a bucket-overflow count that differs; a column that cannot be
        found counts all its reference entries); ``tau_gap``: the widest
        relative gap of a stored tau from the reference's (1 where only one
        of them is infinite)."""
        differ, total, tau_gap = 0, 0, 0.0
        for (call, p), got in stored:
            want = refs[p]
            live = want.idx != reference.INVALID
            total += int(live.sum()) + int(want.dropped.sum())
            if got is None:
                differ += int(live.sum()) + int(want.dropped.sum())
                continue
            same = (got.idx == want.idx) & (got.val == want.val)
            differ += int((~same & (live | (got.idx != reference.INVALID)))
                          .sum())
            differ += int(np.abs(got.dropped.astype(np.int64)
                                 - want.dropped).sum())
            a = got.tau.astype(np.float64)
            b = want.tau.astype(np.float64)
            both = np.isinf(a) & np.isinf(b) & (a == b)
            one = np.isinf(a) ^ np.isinf(b)
            with np.errstate(invalid="ignore", divide="ignore"):
                rel = np.where(both, 0.0, np.where(
                    one, 1.0, np.abs(a - b) / np.abs(b)))
            tau_gap = max(tau_gap, float(np.nanmax(rel, initial=0.0)))
        return {"entries_differ": differ / total if total else 1.0,
                "tau_gap": tau_gap}

    def stored(self) -> list:
        rows = self.traffic["block"]
        where = {nm: i for i, nm in enumerate(self.index._names)}
        out = []
        for call, p in self.acked:
            pos = [where.get(f"i{call}_{c}") for c in range(rows)]
            if any(x is None for x in pos):
                out.append(((call, p), None))
            else:
                out.append(((call, p), drive.stored_rows(
                    self.index, np.asarray(pos))))
        return out

    def check(self) -> dict:
        return self.compare(self.stored(), self.reference_layouts())

    def control_numbers(self) -> dict:
        """The bfloat16 control in the program's place for two passes over
        the pool (as many calls as twice its blocks): its stored rows
        against the reference."""
        ctl = self.reference_layouts("bfloat16")
        calls = 2 * len(ctl)
        stored = [((c, c % len(ctl)), ctl[c % len(ctl)])
                  for c in range(calls)]
        return self.compare(stored, self.reference_layouts())


Kind = BulkIngest
