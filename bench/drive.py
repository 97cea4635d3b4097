"""The two traffic kinds: open-loop queries and closed-loop bulk ingest.

Each kind builds its index through the public ``repro.serve.SketchIndex``
surface, warms up the shapes its window uses, drives the window on the
harness's clock, and afterwards compares what the window produced with the
plain reference (``bench.reference``).  The kind is named by the traffic
file's ``kind``; its parameters come from that file and the configuration.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference
from bench.data import Csr, generator

clock = time.perf_counter


def _sleep_until(t: float) -> None:
    """Sleep to within half a millisecond of ``t``, then spin."""
    while True:
        left = t - clock()
        if left <= 0:
            return
        if left > 0.0015:
            time.sleep(left - 0.001)


def _index(config: dict):
    from repro.serve import SketchIndex
    p = config["index"]
    return SketchIndex(p["m"], n_buckets=p["n_buckets"], slots=p["slots"],
                       seed=p["seed"], head_h=p["head_h"])


def _layout_kw(config: dict) -> dict:
    p = config["index"]
    return dict(m=p["m"], seed=p["seed"], n_buckets=p["n_buckets"],
                slots=p["slots"])


def stored_rows(index, rows: np.ndarray) -> reference.Layout:
    """The index's stored bucketized rows, from its host master copy."""
    return reference.Layout(index._idx[rows], index._val[rows],
                            index._tau[rows], index._dropped[rows])


def _gap(got, want, scale) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    den = np.abs(want) + scale
    return np.where(den > 0, np.abs(got - want) / np.where(den > 0, den, 1),
                    np.abs(got - want))


@dataclasses.dataclass
class Window:
    """What the measured window did, on the harness clock (seconds from its
    start)."""

    seconds: float          # length of the window, to the last completion
    attempted: int
    failed: int
    e2e: dict               # end-to-end metric name -> value
    requests: list          # per request or call: dict of its facts
    facts: dict             # sizes the metric readers need


class OpenQuery:
    """Open-loop queries at a fixed rate against a corpus built in set-up.

    Arrivals are one fixed Poisson trace (exponential gaps of mean
    ``1/rate`` drawn from a constant seed), so every seed offers the same
    load at the same times; the run's seed makes the corpus and the query
    pool (``pool_indexed`` indexed columns asked again, ``pool_fresh``
    columns not in the index, each used equally often) and the order in which the pool's vectors are
    asked.  Whenever the service is free, the backlog of
    due requests goes to ``serve_backlog``.  A request's latency runs from
    its due time to its completion.
    """

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.gen = generator(self.config["data"]["generator"])

    # -- set-up -------------------------------------------------------------

    def setup(self, seconds: float, *, program: bool = True) -> None:
        """Generate the corpus, ingest it through ``add_many``, make the
        query pool and the schedule, and warm up the served entry.  With
        ``program=False`` only the data and schedule are made (the control
        runs without the program)."""
        cfg, tr = self.config, self.traffic
        data, block = cfg["data"], cfg["ingest_block"]
        self.index = _index(cfg) if program else None
        parts = []
        for b in range(-(-cfg["columns"] // block)):
            rows = min(block, cfg["columns"] - b * block)
            blk = self.gen.corpus_block(data, self.seed, b, rows)
            if program:
                self.index.add_many(
                    [f"c{b * block + i}" for i in range(rows)], blk.dense)
            parts.append(Csr(blk.indptr, blk.keys, blk.vals))
        self.corpus = Csr.concat(parts)
        self.names = {f"c{j}": j for j in range(self.corpus.rows)}
        self.pool = self.gen.query_pool(data, self.seed, self.corpus,
                                        tr["pool_indexed"], tr["pool_fresh"])
        self.schedule(tr["rate_per_s"], seconds)
        if not program:
            return
        self.fn = getattr(self.index, tr["entry"])
        self.many = getattr(self.index, tr["entry"] + "_many", None)
        for v in self.pool[:: max(1, len(self.pool) // tr["warmup"])]:
            self._answers([v])

    def schedule(self, rate: float, seconds: float) -> None:
        """Due times (``due``) and pool vectors (``vec``) of the requests."""
        n = max(1, round(rate * seconds))
        gaps = np.random.default_rng(0x6A9).exponential(1.0 / rate, n)
        gaps *= seconds / (gaps.sum() + gaps[0])
        self.due = np.cumsum(gaps)
        self.vec = np.random.default_rng([self.seed, 4]).permutation(
            np.resize(np.arange(len(self.pool)), n))

    def _answers(self, vectors) -> list:
        """Items ``[(name, estimate), ...]`` for each vector, through the
        batch entry when the index has one (an entry's result may carry
        its items as ``.items``)."""
        args = self.traffic["args"]
        if self.many is not None:
            res = self.many(list(vectors), **args)
        else:
            res = [self.fn(v, **args) for v in vectors]
        return [list(getattr(r, "items", r)) for r in res]

    def serve_backlog(self, batch, t0, rec, ann) -> None:
        """Serve due requests ``batch`` (arrival order): one call of the
        batch entry when the index offers it, else one call each."""
        groups = [batch] if self.many is not None else [[r] for r in batch]
        for g in groups:
            s = clock()
            try:
                with ann("bench.serve"):
                    res = self._answers(self.pool[self.vec[g]])
            except Exception as e:   # a failed request counts as missing
                res = [None] * len(g)
                rec["errors"].append(repr(e))
            e = clock()
            for r, ans in zip(g, res):
                rec["start"][r], rec["end"][r] = s - t0, e - t0
                rec["answer"][r] = ans

    # -- window -------------------------------------------------------------

    def window(self, seconds: float, ann) -> Window:
        N = self.due.size
        rec = {"start": np.full(N, np.nan), "end": np.full(N, np.nan),
               "answer": [None] * N, "errors": [], "late": []}
        give_up = seconds + self.traffic["late_limit_s"]
        t0 = clock()
        i = 0
        with ann("bench.window"):
            while i < N:
                now = clock() - t0
                if now > give_up:
                    break
                if self.due[i] > now:
                    with ann("bench.wait"):
                        _sleep_until(t0 + self.due[i])
                    now = clock() - t0
                    rec["late"].append(now - self.due[i])
                j = max(int(np.searchsorted(self.due, now, "right")), i + 1)
                self.serve_backlog(list(range(i, j)), t0, rec, ann)
                i = j
        span = clock() - t0
        ok = np.array([a is not None for a in rec["answer"]])
        lat = np.where(ok, rec["end"] - self.due, np.inf)
        p95 = float(np.percentile(lat, 95)) * 1e3 if N else float("nan")
        self.rec = rec
        reqs = [{"due": float(self.due[r]), "start": float(rec["start"][r]),
                 "end": float(rec["end"][r])} for r in range(N)]
        late = np.asarray(rec["late"] or [0.0])
        return Window(
            seconds=span, attempted=N, failed=int((~ok).sum()),
            e2e={"query_p95_ms": p95}, requests=reqs,
            facts={"corpus_rows": len(self.index), "m": self.config["index"]["m"],
                   "late_start_p99_ms": float(np.percentile(late, 99)) * 1e3,
                   "errors": rec["errors"][:3]})

    # -- correctness --------------------------------------------------------

    def reference_estimates(self, precision: str = "float32"):
        """(pool, D) estimates of every pool vector against the corpus."""
        kw = _layout_kw(self.config)
        n = self.config["data"]["universe"]
        corpus = reference.sketch_columns(
            self.corpus.indptr, self.corpus.keys, self.corpus.vals,
            precision=precision, **kw)
        queries = reference.sketch_dense(self.pool, precision=precision, **kw)
        return reference.estimates(queries, corpus, n, precision=precision)

    def compare(self, answers, ref: np.ndarray) -> dict:
        """The numbers ``correct`` compares, over every answered request:
        ``est_gap``, the widest gap of a returned estimate from the
        reference's estimate of that column, relative to the reference's
        own magnitude plus its largest top-k magnitude; ``topk_shortfall``,
        the most a returned column falls below the reference's k-th best,
        relative to the same scale (1 for a missing or unknown column)."""
        k = self.traffic["k"]
        D = ref.shape[1]
        want_n = min(k, D)
        gap, short = 0.0, 0.0
        for r, items in enumerate(answers):
            if items is None:
                continue
            est = ref[self.vec[r]]
            best = np.sort(est)[::-1][:want_n]
            scale = float(np.abs(best).max()) if best.size else 0.0
            rows = [self.names.get(nm, -1) for nm, _ in items]
            if len(items) != want_n or min(rows, default=0) < 0:
                short = max(short, 1.0)
                continue
            rows = np.asarray(rows)
            got = np.array([e for _, e in items], np.float64)
            gap = max(gap, float(_gap(got, est[rows], scale).max()))
            fall = (best[-1] - est[rows]) / (scale if scale > 0 else 1.0)
            short = max(short, float(np.max(fall, initial=0.0)))
        return {"est_gap": gap, "topk_shortfall": short}

    def check(self) -> dict:
        return self.compare(self.rec["answer"], self.reference_estimates())

    def control_numbers(self) -> dict:
        """The bfloat16 control in the program's place, answering every
        request of the schedule, against the reference."""
        ctl = self.control_answers(self.reference_estimates("bfloat16"))
        return self.compare(ctl, self.reference_estimates())

    def control_answers(self, est: np.ndarray) -> list:
        """The bfloat16 control put in the program's place: per request, the
        top-k of the control's own estimates."""
        k = self.traffic["k"]
        names = [f"c{j}" for j in range(est.shape[1])]
        out = []
        for r in range(self.due.size):
            e = est[self.vec[r]]
            order = np.lexsort((np.arange(e.size), -e))[:k]
            out.append([(names[j], float(e[j])) for j in order])
        return out


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
        warm = _index(self.config)
        warm.add_many([f"w{i}" for i in range(rows)], self.blocks[0].dense)
        del warm
        self.index = _index(self.config)

    def window(self, seconds: float, ann) -> Window:
        rows = self.traffic["block"]
        calls, failed, reqs, errors = 0, 0, [], []
        self.acked = []           # (call, pool block) of each stored call
        t0 = clock()
        with ann("bench.window"):
            while clock() - t0 < seconds:
                p = calls % len(self.blocks)
                s = clock()
                try:
                    with ann("bench.add_many"):
                        self.index.add_many(
                            [f"i{calls}_{c}" for c in range(rows)],
                            self.blocks[p].dense)
                    self.acked.append((calls, p))
                except Exception as e:
                    failed += 1
                    errors.append(repr(e))
                reqs.append({"start": s - t0, "end": clock() - t0})
                calls += 1
        span = clock() - t0
        return Window(
            seconds=span, attempted=calls, failed=failed,
            e2e={"ingest_cols_per_s": len(self.acked) * rows / span},
            requests=reqs,
            facts={"block_rows": rows,
                   "universe": self.config["data"]["universe"],
                   "errors": errors[:3]})

    def reference_layouts(self, precision: str = "float32") -> list:
        kw = _layout_kw(self.config)
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
                out.append(((call, p), stored_rows(self.index,
                                                   np.asarray(pos))))
        return out

    def check(self) -> dict:
        return self.compare(self.stored(), self.reference_layouts())

    def control_numbers(self, calls: int) -> dict:
        """The bfloat16 control in the program's place for ``calls`` calls
        of the window: its stored rows against the reference."""
        ctl = self.reference_layouts("bfloat16")
        stored = [((c, c % len(ctl)), ctl[c % len(ctl)])
                  for c in range(calls)]
        return self.compare(stored, self.reference_layouts())


KINDS = {"open_query": OpenQuery, "bulk_ingest": BulkIngest}


def traffic_kind(cell):
    kind = cell.traffic["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}; one of "
                         f"{sorted(KINDS)}")
    return KINDS[kind]

