"""``exact_dot``: closed-loop ``SketchIndex.query`` of sparse vectors
that every sketch keeps whole, checked against their exact inner
products."""
import numpy as np

from bench import drive, spec


class ExactDot:
    def __init__(self, cell, seed):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.ref = spec.load_file(cell.bench_dir, "exact_dot_reference.py")

    def _vectors(self, rng, rows):
        n, k = self.config["universe"], self.config["nonzeros"]
        out = np.zeros((rows, n), np.float32)
        for r in range(rows):
            out[r, rng.choice(n, k, replace=False)] = rng.uniform(0.5, 2, k)
        return out

    def setup(self, seconds, *, program=True):
        rng = np.random.default_rng([self.seed, 9])
        self.corpus = self._vectors(rng, self.config["columns"])
        self.pool = self._vectors(rng, self.traffic["pool"])
        if not program:
            return
        from repro.serve import SketchIndex
        p = self.config["index"]
        self.index = SketchIndex(p["m"], n_buckets=p["n_buckets"],
                                 slots=p["slots"], seed=p["seed"],
                                 head_h=p["head_h"])
        self.index.add_many([f"c{j}" for j in range(len(self.corpus))],
                            self.corpus)
        self.index.query(self.pool[0])

    def window(self, seconds, ann):
        self.answers, reqs = [], []
        t0 = drive.clock()
        with ann("bench.window"):
            while drive.clock() - t0 < seconds:
                s = drive.clock()
                v = self.pool[len(self.answers) % len(self.pool)]
                with ann("bench.serve"):
                    self.answers.append(self.index.query(v))
                reqs.append({"start": s - t0, "end": drive.clock() - t0})
        lat = [r["end"] - r["start"] for r in reqs]
        return drive.Window(
            seconds=drive.clock() - t0, attempted=len(reqs), failed=0,
            e2e={"query_p95_ms": float(np.percentile(lat, 95)) * 1e3},
            requests=reqs, facts={})

    def _compare(self, answers, exact):
        worst = 0.0
        for r, items in enumerate(answers):
            want = exact[r % len(exact)]
            got = dict(items)
            est = [got.get(f"c{j}", np.inf) for j in range(want.size)]
            worst = max(worst, float(drive.gap(
                est, want, np.abs(want).max()).max()))
        return {"dot_gap": worst}

    def check(self):
        return self._compare(self.answers, self.ref.dots(self.corpus,
                                                         self.pool))

    def control_numbers(self):
        ctl = self.ref.dots(self.corpus, self.pool, "bfloat16")
        answers = [[(f"c{j}", e) for j, e in enumerate(row)] for row in ctl]
        return self._compare(answers, self.ref.dots(self.corpus, self.pool))


Kind = ExactDot
