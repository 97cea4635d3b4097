"""``open_query``: open-loop queries at a fixed rate against a
``SketchIndex`` corpus built in set-up, checked against the Algorithm-2
inner-product reference (``bench.reference``).

Traffic keys: ``entry`` (the index's method, e.g. ``query``), ``args``,
``k``, ``rate_per_s``, ``pool_indexed``, ``pool_fresh``, ``warmup``,
``late_limit_s``.  Configuration keys: ``index``, ``columns``,
``ingest_block``, ``data`` (its generator's ``corpus_block`` and
``query_pool``).
"""
from __future__ import annotations

import numpy as np

from bench import drive, reference
from bench.data import Csr, generator


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
        self.index = drive.sketch_index(cfg) if program else None
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

    def serve_one(self, n: int) -> list:
        """Serve the pool's ``n``-th vector (modulo the pool) closed loop:
        its answer's items, once it is answered."""
        return self._answers([self.pool[n % len(self.pool)]])[0]

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
            s = drive.clock()
            try:
                with ann("bench.serve"):
                    res = self._answers(self.pool[self.vec[g]])
            except Exception as e:   # a failed request counts as missing
                res = [None] * len(g)
                rec["errors"].append(repr(e))
            e = drive.clock()
            for r, ans in zip(g, res):
                rec["start"][r], rec["end"][r] = s - t0, e - t0
                rec["answer"][r] = ans

    # -- window -------------------------------------------------------------

    def window(self, seconds: float, ann) -> drive.Window:
        N = self.due.size
        rec = {"start": np.full(N, np.nan), "end": np.full(N, np.nan),
               "answer": [None] * N, "errors": [], "late": []}
        give_up = seconds + self.traffic["late_limit_s"]
        t0 = drive.clock()
        i = 0
        with ann("bench.window"):
            while i < N:
                now = drive.clock() - t0
                if now > give_up:
                    break
                if self.due[i] > now:
                    with ann("bench.wait"):
                        drive.sleep_until(t0 + self.due[i])
                    now = drive.clock() - t0
                    rec["late"].append(now - self.due[i])
                j = max(int(np.searchsorted(self.due, now, "right")), i + 1)
                self.serve_backlog(list(range(i, j)), t0, rec, ann)
                i = j
        span = drive.clock() - t0
        ok = np.array([a is not None for a in rec["answer"]])
        lat = np.where(ok, rec["end"] - self.due, np.inf)
        p95 = float(np.percentile(lat, 95)) * 1e3 if N else float("nan")
        self.rec = rec
        reqs = [{"due": float(self.due[r]), "start": float(rec["start"][r]),
                 "end": float(rec["end"][r])} for r in range(N)]
        late = np.asarray(rec["late"] or [0.0])
        return drive.Window(
            seconds=span, attempted=N, failed=int((~ok).sum()),
            e2e={"query_p95_ms": p95}, requests=reqs,
            facts={"corpus_rows": len(self.index), "m": self.config["index"]["m"],
                   "late_start_p99_ms": float(np.percentile(late, 99)) * 1e3,
                   "errors": rec["errors"][:3]})

    # -- correctness --------------------------------------------------------

    def reference_estimates(self, precision: str = "float32"):
        """(pool, D) estimates of every pool vector against the corpus."""
        kw = drive.layout_kw(self.config)
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
            gap = max(gap, float(drive.gap(got, est[rows], scale).max()))
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


Kind = OpenQuery
