"""The one traffic generator: closed-loop clients driven by a traffic file.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters; the
statements come from the configuration's dataset module (its
``PUBLISHED`` queries, ``TEMPLATES`` and ``draw_params``):

* ``clients``: client threads, each with its own connection, in a closed
  loop (the next query is sent when the last one's rows are in);
* ``published_share``, ``published_order`` and ``queries``: the share of
  requests that are one of the published queries, how a client picks them,
  and which: ``rotation`` (the order of ``queries``, stream ``s`` starting
  ``s`` places on) or ``zipf`` (Zipf(``zipf_alpha``) over ``queries`` as a
  fixed ranking, the first most often, drawn from ``stream_seed``);
* streams: each client runs one of ``clients`` fixed request streams, the
  run's seed rotating which.  Request ``k`` of stream ``s`` is fresh when
  ``(k + s) * (1 - published_share)`` passes a whole number, so every
  stream's requests are fresh in the same share from its first ones on,
  and every seed sends the same requests in the same numbers;
* ``fresh_pool_per_s``: the fresh requests are templates
  filled with drawn parameters.  They come from one pool of distinct
  statements, ``fresh_pool_per_s`` per second of window, each sent at most
  once, so none is answered from the result cache.  The pool is drawn from
  ``fresh_pool_seed``, the same for every run, in rounds of one statement
  of each template, and is sent round by round; the run's seed draws only
  the order within each round.  So every seed sends the same statements
  in nearly the same order, every prefix of the pool holds the templates
  in nearly equal numbers, and every kernel program a seed needs is in
  the compile cache after a checkout's first run;
* ``session``: connection settings the mix pins (caches on or off);
* ``warm_result_cache``: whether set-up leaves the published queries'
  answers in the result cache, as a live dashboard's are;
* ``reference_fresh``: at most this many distinct fresh statements are
  checked against the reference after the window, drawn from the seed;
* ``profile``: ``start_s`` and ``seconds`` of the profiler trace in a
  traced run.

The seed draws the tables, the order within each round of the pool, which
client runs which stream, and the reference's sample; what is sent, and
how much, is the same on every seed.  A run of ``seconds`` sends no query after
the window closes, and waits for those in flight.
"""
from __future__ import annotations

import itertools
import math
import threading
import time

import numpy as np

QUERY_TIMEOUT_S = 150.0


class Traffic:
    """The request streams of one traffic mix over one dataset for one
    seed."""

    def __init__(self, spec: dict, dataset, seed: int, seconds: float):
        self.spec = spec
        self.dataset = dataset
        self.seed = seed
        self.published = published = dataset.PUBLISHED
        self.names = list(spec.get("queries") or sorted(published))
        fresh_share = 1.0 - float(spec["published_share"])
        n_pool = (math.ceil(spec.get("fresh_pool_per_s", 0) * seconds)
                  if fresh_share > 0 else 0)
        rounds = _fresh_rounds(
            dataset, np.random.default_rng(spec.get("fresh_pool_seed", 0)),
            math.ceil(n_pool / len(dataset.TEMPLATES)),
            set(published.values()))
        rng = np.random.default_rng([seed, 2])
        self.pool = []
        for round_ in rounds:
            self.pool += [round_[i] for i in rng.permutation(len(round_))]
        del self.pool[n_pool:]
        self._next_fresh = 0
        self.fresh_exhausted = 0
        self._lock = threading.Lock()
        order = spec["published_order"]
        if order == "zipf":
            p = np.arange(1, len(self.names) + 1, dtype=float) \
                ** -spec["zipf_alpha"]
            self._zipf = p / p.sum()
        elif order != "rotation":
            raise ValueError(f"unknown published_order {order!r}")

    def statements(self) -> list:
        """Every distinct ``(label, sql)`` the window can send."""
        return [(n, self.published[n]) for n in self.names] + self.pool

    def stream(self, client: int):
        """Yield ``(label, sql)`` requests for one client, without end."""
        s = (client + self.seed) % int(self.spec["clients"])
        rng = np.random.default_rng([self.spec.get("stream_seed", 0), 4, s])
        order = self.spec["published_order"]
        fresh_share = 1.0 - float(self.spec["published_share"])
        names = self.names
        i = s
        for k in itertools.count(s):
            if math.floor((k + 1) * fresh_share) > math.floor(k * fresh_share):
                fresh = self._take_fresh()
                if fresh is not None:
                    yield fresh
                    continue
            if order == "zipf":
                name = names[int(rng.choice(len(names), p=self._zipf))]
            else:
                name = names[i % len(names)]
                i += 1
            yield name, self.published[name]

    def _take_fresh(self):
        with self._lock:
            if self._next_fresh >= len(self.pool):
                self.fresh_exhausted += 1
                return None
            self._next_fresh += 1
            return self.pool[self._next_fresh - 1]

    def reference_sample(self, sent: set) -> set:
        """The statements checked against the reference: every published
        one sent, and at most ``reference_fresh`` distinct fresh ones,
        drawn from the seed."""
        published = {self.published[n] for n in self.names}
        fresh = sorted(sent - published)
        cap = int(self.spec.get("reference_fresh", len(fresh)))
        if len(fresh) > cap:
            rng = np.random.default_rng([self.seed, 5])
            fresh = [fresh[i] for i in rng.choice(len(fresh), cap,
                                                  replace=False)]
        return (sent & published) | set(fresh)


def _fresh_rounds(dataset, rng, n: int, exclude: set) -> list:
    """``n`` rounds of distinct fresh statements, one of each of the
    dataset's templates a round, so that every prefix of a pool taken round
    by round holds the templates in nearly equal numbers."""
    rounds, seen = [], set(exclude)
    for _ in range(n):
        round_ = []
        for name in sorted(dataset.TEMPLATES):
            for _ in range(1000):
                sql = dataset.TEMPLATES[name].format(
                    **dataset.draw_params(rng))
                if sql not in seen:
                    break
            else:
                raise ValueError(f"template {name} has fewer than {n} "
                                 f"distinct statements")
            seen.add(sql)
            round_.append((f"fresh:{name}", sql))
        rounds.append(round_)
    return rounds


def run_window(traffic: Traffic, conns: list, seconds: float, annotate,
               on_done=None, during=None, clock=time.perf_counter):
    """Run every client's closed loop for ``seconds``; return ``(t0,
    records)``.

    No query starts after the window closes; those in flight then are
    waited for.  Each record holds the label, SQL, submit and done times,
    and the rows, or the error.  ``annotate(label)`` is a context manager
    around each query; ``on_done(record, handle)`` adds what a run needs
    from a finished handle; ``during(t0)`` runs on the calling thread while
    the clients do.
    """
    records, lock = [], threading.Lock()
    start = threading.Event()
    window = {}

    def client(cid, conn):
        stream = traffic.stream(cid)
        mine = []
        start.wait()
        deadline = window["t0"] + seconds
        while clock() < deadline:
            label, sql = next(stream)
            rec = {"label": label, "sql": sql, "t_submit": clock()}
            try:
                with annotate(label):
                    h = conn.execute_async(sql)
                    rec["rows"] = h.result(QUERY_TIMEOUT_S).fetchall()
                rec["t_done"] = clock()
                if on_done is not None:
                    on_done(rec, h)
            except Exception as exc:  # noqa: BLE001 - counted as failed
                rec["t_done"] = clock()
                rec["error"] = f"{type(exc).__name__}: {exc}"
            mine.append(rec)
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=client, args=(i, c), daemon=True)
               for i, c in enumerate(conns)]
    for t in threads:
        t.start()
    window["t0"] = clock()
    start.set()
    if during is not None:
        during(window["t0"])
    for t in threads:
        t.join()
    return window["t0"], records
