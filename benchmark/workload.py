"""The one general traffic generator and the load loops.

A traffic mix is a data file (``traffic/<name>.json``): statement
classes (an nGQL template, the semantics the plain reference answers
it by, whether it is a traversal), groups of load (``open``: a Poisson
schedule at ``rate_per_s``; ``closed``: ``clients`` callers that each
wait for their reply, working through ``sequence`` statements), the
start-key distribution, the warm-up and the traced sub-window.  Every
statement has a key of its own, as many vertices as its semantics
module's ``ARITY`` says (one unless it says otherwise; the template
takes ``{v}``, or ``{v0}``, ``{v1}``, ... where there are more):
nothing is replayed unless a closed group outruns its ``sequence``.

What the seed changes and what it does not: the statement sequence and
the arrival gaps are drawn from the configuration's ``structure_seed``
over STRUCTURAL vertex ids, so every ``--seed`` offers the same
multiset of work; ``--seed`` relabels the vertices
(deploy.label_data), and permutes the sequence and the gaps.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import List, Optional, Tuple, Union

import numpy as np

from . import reference
from .deploy import response_problems


SHUFFLE_BLOCK = 256


class Mix:
    """The statement sequences of one traffic file on one labelled
    data set: per group and phase, a class and a key per position
    (and a due time where the loop is open)."""

    def __init__(self, traffic: dict, data: dict, structure_seed: int,
                 seed: int, seconds: float):
        self.traffic = traffic
        self.class_names = list(traffic["classes"])
        self.classes = [traffic["classes"][n] for n in self.class_names]
        self.arity = [int(getattr(reference.semantics_module(
            c["semantics"]["kind"]), "ARITY", 1)) for c in self.classes]
        if traffic["start_keys"]["distribution"] != "uniform":
            raise ValueError("start_keys.distribution must be 'uniform'")
        self._cand = data["structural_with_out_edge"]
        self._label = data["perm"]
        self._structure_seed = structure_seed
        self.groups = []
        for gi, g in enumerate(traffic["groups"]):
            self.groups.append({
                "spec": g,
                "measured": self._sequence(g, gi, seed, seconds, 0),
                "warmup": self._sequence(
                    g, gi, seed, float(traffic["warmup"]["seconds"]), 1)})

    def _sequence(self, g: dict, gi: int, seed: int, seconds: float,
                  phase: int) -> dict:
        """(class index, key[, due offset]) per position: drawn from
        the structure seed, every vertex of a key uniformly from the
        vertices with an out-edge (none twice in one place of the
        key; a key may, once in half a million, name one vertex in
        two places), put in another order by ``seed`` (a closed
        group's inside blocks of SHUFFLE_BLOCK positions).  ``key`` is
        one column where every class of the group has arity 1, and
        one column a place of the widest class's key where not: each
        further place draws from a generator of its own, so a mix of
        arity 1 is offered as it was before there were others."""
        rng = np.random.default_rng([self._structure_seed, 0x5e9, gi,
                                     phase])
        order = np.random.default_rng([seed, 0x0d3, gi, phase])
        names = list(g["shares"])
        shares = np.asarray([g["shares"][n] for n in names], float)
        if g["loop"] == "open":
            n = max(1, int(round(float(g["rate_per_s"]) * seconds)))
        else:
            n = int(g["sequence"])
        cls = rng.choice([self.class_names.index(x) for x in names],
                         size=n, p=shares / shares.sum())
        key = self._label[rng.choice(self._cand, size=n,
                                     replace=n > len(self._cand))]
        arity = max(self.arity[self.class_names.index(x)] for x in names)
        if arity > 1:
            key = np.stack([key] + [self._label[np.random.default_rng(
                [self._structure_seed, 0x5e9, gi, phase, place]).choice(
                    self._cand, size=n, replace=n > len(self._cand))]
                for place in range(1, arity)], axis=1)
        # a closed group completes a prefix of its sequence, so its
        # order changes inside blocks only: every seed then works
        # through the same statements, whatever prefix it reaches
        block = n if g["loop"] == "open" else SHUFFLE_BLOCK
        shuffle = np.concatenate([lo + order.permutation(min(block, n - lo))
                                  for lo in range(0, n, block)])
        out = {"cls": cls[shuffle], "key": key[shuffle]}
        if g["loop"] == "open":
            gaps = rng.exponential(1.0, n + 1)
            gaps = gaps[:n][order.permutation(n)] * (seconds / gaps.sum())
            out["due"] = np.cumsum(gaps)
        return out

    def at(self, seq: dict, i: int) -> Tuple[int, Union[int, tuple]]:
        """(class index, key) of position ``i``: the key one vertex
        label where the class's arity is 1, else a tuple of them."""
        ci, key = int(seq["cls"][i]), np.atleast_1d(seq["key"][i])
        if self.arity[ci] == 1:
            return ci, int(key[0])
        return ci, tuple(int(k) for k in key[:self.arity[ci]])

    def statement(self, ci: int, key) -> str:
        """The class's template on its key: ``{v}`` takes the one
        start vertex of a class of arity 1, or several (a warm-up
        statement: ``key`` is then a sequence); ``{v0}``, ``{v1}``,
        ... take the places of a longer key."""
        starts = [key] if np.isscalar(key) else key
        template = self.classes[ci]["template"]
        if self.arity[ci] == 1:
            return template.format(v=", ".join(str(int(k)) for k in starts))
        return template.format(**{f"v{place}": int(k)
                                  for place, k in enumerate(starts)})

    def is_traversal(self, ci: int) -> bool:
        return bool(self.classes[ci].get("traversal"))

    def warm(self, statements: int, starts: int, salt: int
             ) -> List[Tuple[int, tuple]]:
        """``statements`` traversal statements of ``starts`` start
        vertices each (of one key each where the class's arity is
        more than 1), for a warm-up step (the same for every seed, up
        to the labels)."""
        trav = [i for i in range(len(self.classes)) if self.is_traversal(i)]
        if not trav:
            return []
        rng = np.random.default_rng([self._structure_seed, 0xb07, salt])
        cls = [trav[(j + salt) % len(trav)] for j in range(statements)]
        # a class of arity 1 takes ``starts`` vertices, another its key
        take = [starts if self.arity[ci] == 1 else self.arity[ci]
                for ci in cls]
        keys = self._label[rng.choice(
            self._cand, size=(statements, max([starts] + take)))]
        return [(ci, tuple(keys[j][:take[j]]))
                for j, ci in enumerate(cls)]


def columns_of(resp) -> reference.Answer:
    """What a client takes from a response, in the reference's two
    forms: the columnar payload's int64 columns as they are, anything
    else as a list of row tuples."""
    rows = resp.rows
    cols = getattr(rows, "_cols", None)
    if cols is not None and cols and all(
            isinstance(c, np.ndarray) and c.dtype.kind in "iu"
            for c in cols):
        return tuple(np.asarray(c, np.int64) for c in cols)
    return [tuple(r) for r in (rows or [])]


class Driver:
    """Runs one phase (warm-up or the measured window) of every group
    and keeps one record per statement sent."""

    def __init__(self, deployment, mix: Mix, keep_share: float,
                 keep_rows_cap: int, seed: int):
        self.dep = deployment
        self.mix = mix
        self.keep_share = keep_share
        self.keep_rows_cap = keep_rows_cap
        self.seed = seed
        self._lock = threading.Lock()
        self._kept_rows = 0
        self.records: List[dict] = []
        self.largest: Optional[dict] = None
        self.errors: List[str] = []

    def _one(self, client, gi: int, pos: int, ci: int, key,
             due: float, keep: bool) -> None:
        stmt = self.mix.statement(ci, key)
        sent = time.perf_counter()
        resp = client.execute(stmt)
        done = time.perf_counter()
        problems = response_problems(resp)
        rec = {"group": gi, "pos": pos, "cls": ci, "key": key, "due": due,
               "sent": sent, "done": done,
               "problem": "; ".join(problems) or None}
        if not problems:
            ans = columns_of(resp)
            rec["digest"] = reference.digest(ans)
            rec["rows"] = n = reference.n_rows(ans)
            with self._lock:
                if keep and self._kept_rows + n <= self.keep_rows_cap:
                    self._kept_rows += n
                    rec["answer"] = ans
                if self.largest is None or n > self.largest["rows"]:
                    self.largest = {**rec, "answer": ans,
                                    "index": len(self.records)}
                self.records.append(rec)
                return
        with self._lock:
            self.records.append(rec)

    def _closed_client(self, gi: int, seq: dict, counter, keep: np.ndarray,
                       t0: float, t_end: float) -> None:
        try:
            client = self.dep.client()
            while time.perf_counter() < t0:
                time.sleep(0.001)
            n = len(seq["cls"])
            while True:
                pos = next(counter)
                now = time.perf_counter()
                if now >= t_end:
                    return
                i = pos % n
                self._one(client, gi, pos, *self.mix.at(seq, i), now,
                          bool(keep[i]))
        except Exception as e:   # noqa: BLE001 — a dead client is a
            with self._lock:     # failed run, reported, not a lost one
                self.errors.append(f"client of group {gi}: "
                                   f"{type(e).__name__}: {e}")

    def _open_worker(self, gi: int, seq: dict, jobs: "queue.Queue",
                     keep: np.ndarray, t0: float) -> None:
        try:
            client = self.dep.client()
            while True:
                pos = jobs.get()
                if pos is None:
                    return
                self._one(client, gi, pos, *self.mix.at(seq, pos),
                          t0 + float(seq["due"][pos]), bool(keep[pos]))
        except Exception as e:   # noqa: BLE001 — as above
            with self._lock:
                self.errors.append(f"worker of group {gi}: "
                                   f"{type(e).__name__}: {e}")

    def _open_schedule(self, seq: dict, jobs: "queue.Queue", workers: int,
                       t0: float) -> None:
        for pos in range(len(seq["due"])):
            wait = t0 + float(seq["due"][pos]) - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            jobs.put(pos)
        for _ in range(workers):
            jobs.put(None)

    def run(self, phase: str, seconds: float, lead_s: float = 0.5,
            on_start=None) -> Tuple[float, float]:
        """Drive every group for ``seconds``; returns (t0, t_end) on
        the perf_counter clock.  Statements in flight at t_end are
        waited for; an open group sends every arrival of its schedule."""
        threads: List[threading.Thread] = []
        t0 = time.perf_counter() + lead_s
        t_end = t0 + seconds
        for gi, g in enumerate(self.mix.groups):
            seq, spec = g[phase], g["spec"]
            keep = np.random.default_rng(
                [self.seed, 0x4ee9, gi]).random(len(seq["cls"])) \
                < self.keep_share
            if spec["loop"] == "closed":
                counter = itertools.count()
                for _ in range(int(spec["clients"])):
                    threads.append(threading.Thread(
                        target=self._closed_client,
                        args=(gi, seq, counter, keep, t0, t_end)))
            else:
                jobs: "queue.Queue" = queue.Queue()
                workers = int(spec["workers"])
                for _ in range(workers):
                    threads.append(threading.Thread(
                        target=self._open_worker,
                        args=(gi, seq, jobs, keep, t0)))
                threads.append(threading.Thread(
                    target=self._open_schedule,
                    args=(seq, jobs, workers, t0)))
        for t in threads:
            t.start()
        if on_start is not None:
            on_start(t0)
        for t in threads:
            t.join()
        return t0, t_end

    def run_burst(self, pairs: List[Tuple[int, tuple]]) -> None:
        """Send the given statements at once, one thread each (a
        warm-up step: the shapes a crowded tick needs)."""
        def one(ci: int, key: tuple) -> None:
            try:
                self._one(self.dep.client(), -1, -1, ci, key,
                          time.perf_counter(), False)
            except Exception as e:   # noqa: BLE001 — as above
                with self._lock:
                    self.errors.append(f"burst: {type(e).__name__}: {e}")
        threads = [threading.Thread(target=one, args=p) for p in pairs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
