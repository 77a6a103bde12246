"""The one general traffic generator: it reads a traffic mix
(``bench/traffic/<name>.json``) and drives the program through its public
API, closed loop, one request at a time.

- ``batch``: ``Session.run`` of the flow over a whole fact table; runs
  take the fact tables in turn, so no run reads the facts of the one
  before.  One run is one request.
- ``serve``: ``ServeSession.tick`` with ``tick_rows`` consecutive fact
  rows; ticks take the fact tables in turn and walk each table slice by
  slice.  One tick is one request.

A *feed* says what request ``n`` carries and what the reference answers to
it, and compares answers; a *loop* is a feed that also drives the program
and keeps every answer it returned.  The control (``bench/control.py``)
uses the feeds alone.
"""
from __future__ import annotations

import time
import traceback
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import check, flows, reference

Table = Dict[str, np.ndarray]
Outputs = List[Tuple[int, Table]]


@dataclass
class Record:
    """One request: host-clock start and end, fact rows it carried, and
    whether it came back clean (no exception, retry, degradation or
    dead letter)."""
    start: float
    end: float
    rows: int
    ok: bool
    #: index of the fact table the request read
    fact: int


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _host(table) -> Table:
    return {k: np.asarray(v) for k, v in table.items()}


def _faults(stats) -> int:
    get = (stats.get if isinstance(stats, dict)
           else lambda k, d=0: getattr(stats, k, d))
    return sum(int(get(k, 0) or 0)
               for k in ("degradations", "retries", "faults_injected"))


class BatchFeed:
    """Request ``n`` is a whole run over fact table ``n % tables``."""

    unit = "run"

    def __init__(self, config: dict, traffic: dict, flow: dict, data):
        self.config, self.traffic, self.flow, self.data = (config, traffic,
                                                           flow, data)
        self.rows = [len(next(iter(f.values()))) for f in data.facts]

    def inputs(self) -> int:
        """Distinct inputs: one per fact table."""
        return len(self.data.facts)

    def fact_of(self, n: int) -> int:
        return n % len(self.data.facts)

    def reference(self, n: int, precision: str = "float64",
                  state: str = "float64") -> Dict[int, Table]:
        """The sink table of each of the first ``n`` requests."""
        per_fact = {f: reference.run(self.flow, self.data.facts[f],
                                     self.data.dims, precision)
                    for f in sorted({self.fact_of(i) for i in range(n)})}
        return {i: per_fact[self.fact_of(i)] for i in range(n)}

    def compare(self, outputs: Outputs, expected: Dict[int, Table]
                ) -> List[Dict[str, float]]:
        cols = reference.aggregate_columns(self.flow)
        return [check.compare(got, expected[i], cols) for i, got in outputs]

    def shapes(self, n: int) -> Dict[int, dict]:
        """Rows each fact table sends to the group-by and the groups they
        make, for the rooflines: from the reference's partials."""
        out = {}
        for f in sorted({self.fact_of(i) for i in range(n)}):
            p = reference.partials(self.flow, self.data.facts[f],
                                   self.data.dims)
            out[f] = {"agg_rows": int(p.counts.sum()),
                      "groups": int(np.count_nonzero(p.counts))}
        return out


class ServeFeed:
    """Request ``n`` is a tick of ``tick_rows`` rows: slice
    ``(n // tables) % slices`` of fact table ``n % tables``."""

    unit = "tick"

    def __init__(self, config: dict, traffic: dict, flow: dict, data):
        self.config, self.traffic, self.flow, self.data = (config, traffic,
                                                           flow, data)
        self.tick_rows = int(traffic["tick_rows"])
        rows = min(len(next(iter(f.values()))) for f in data.facts)
        self.slices = rows // self.tick_rows
        if self.slices < 1:
            raise ValueError(f"fact tables of {rows} rows hold no tick of "
                             f"{self.tick_rows}")

    def inputs(self) -> int:
        """Distinct inputs: every slice of every table."""
        return len(self.data.facts) * self.slices

    def batch_of(self, n: int) -> Tuple[int, int]:
        """(fact table, slice) that tick ``n`` carries."""
        tables = len(self.data.facts)
        return n % tables, (n // tables) % self.slices

    def fact_of(self, n: int) -> int:
        return self.batch_of(n)[0]

    def columns(self, n: int) -> Table:
        fact, sl = self.batch_of(n)
        lo = sl * self.tick_rows
        return {c: a[lo:lo + self.tick_rows]
                for c, a in self.data.facts[fact].items()}

    def reference(self, n: int, precision: str = "float64",
                  state: str = "float64") -> Dict[int, Table]:
        """The delta each of the first ``n`` ticks should emit: the merged
        state of every tick up to it, for the groups the tick touched."""
        cache: Dict[Tuple[int, int], reference.Partials] = {}
        acc: Optional[reference.Partials] = None
        out: Dict[int, Table] = {}
        keys = reference.aggregate_columns(self.flow)["keys"]
        for tick in range(n):
            key = self.batch_of(tick)
            if key not in cache:
                cache[key] = reference.partials(self.flow, self.columns(tick),
                                                self.data.dims, precision)
            part = cache[key]
            acc = reference.merge(acc, part, state)
            table = reference.finalize(acc, self.flow, state)
            touched = {tuple(int(part.keys[g][i]) for g in keys)
                       for i in np.flatnonzero(part.counts)}
            rows = len(table[keys[0]]) if keys else 1
            keep = np.array([tuple(int(table[g][i]) for g in keys) in touched
                             for i in range(rows)], dtype=bool)
            out[tick] = {c: v[keep] for c, v in table.items()}
        return out

    def compare(self, outputs: Outputs, expected: Dict[int, Table]
                ) -> List[Dict[str, float]]:
        """Every tick's delta, and, when every tick answered, the replay of
        all deltas (``repro.replay_deltas``) against the state after the
        last tick."""
        import repro
        cols = reference.aggregate_columns(self.flow)
        keys = cols["keys"]
        out = [check.compare(check.sort_by_keys(got, keys),
                             check.sort_by_keys(expected[t], keys), cols)
               for t, got in outputs]
        if outputs and len(outputs) == len(expected):
            replay = repro.replay_deltas([d for _, d in outputs],
                                         group_by=keys)
            out.append(check.compare(_host(replay),
                                     self.final_state(expected), cols))
        return out

    def final_state(self, expected: Dict[int, Table]) -> Table:
        """Every group's row from the last tick that touched it, in
        ascending key order."""
        keys = reference.aggregate_columns(self.flow)["keys"]
        latest: Dict[tuple, Tuple[int, int]] = {}
        for t in sorted(expected):
            tab = expected[t]
            for i in range(len(tab[keys[0]]) if keys else 1):
                latest[tuple(int(tab[k][i]) for k in keys)] = (t, i)
        cols = expected[min(expected)]
        table = {c: np.array([expected[t][c][i] for t, i in latest.values()],
                             dtype=cols[c].dtype) for c in cols}
        return check.sort_by_keys(table, keys)

    def shapes(self, n: int) -> Dict[int, dict]:
        return {}


class _Loop:
    """What both loops share: the request counter, every answer, and the
    warm-up."""

    def _init_loop(self) -> None:
        self.n = 0
        #: (request index, answer) of every request that returned
        self.outputs: Outputs = []

    def warm_up(self) -> List[Record]:
        """The traffic's warm-up requests: ``"all"`` sends every distinct
        input once, so that each shape the window will see has compiled; a
        number sends that many."""
        n = self.traffic["warm_up"]
        n = self.inputs() if n == "all" else int(n)
        return [self.step() for _ in range(n)]

    def step(self) -> Record:
        i = self.n
        self.n += 1
        with _annotation(f"bench.{self.unit}"):
            t0 = time.perf_counter()
            try:
                answer, ok = self._request(i)
            except Exception:
                traceback.print_exc()
                answer, ok = None, False
            t1 = time.perf_counter()
        if answer is not None:
            self.outputs.append((i, answer))
        return Record(t0, t1, self._rows(i), ok, self.fact_of(i))

    def check(self) -> List[Dict[str, float]]:
        """Every answer against the reference's."""
        return self.compare(self.outputs, self.reference(self.n))


class BatchLoop(BatchFeed, _Loop):
    """Closed-loop batch runs of one flow (``Session.run``)."""

    def __init__(self, config: dict, traffic: dict, flow: dict, data):
        import repro
        super().__init__(config, traffic, flow, data)
        self._init_loop()
        self.session = repro.Session(backend=config["backend"],
                                     metadata=None)
        self.built = flows.build(flow, data.facts[0], data.dims)
        self.source = flows.source_of(self.built)

    def _rows(self, n: int) -> int:
        return self.rows[self.fact_of(n)]

    def _request(self, n: int):
        self.source.set_data(self.data.facts[self.fact_of(n)])
        res = self.session.run(self.built, engine=self.config["engine"],
                               fuse=self.config["fuse"])
        return _host(res.table), _faults(res.run) == 0

    def close(self) -> None:
        self.session = self.built = self.source = None


class ServeLoop(ServeFeed, _Loop):
    """Closed-loop ticks into one resident serving session
    (``Session.serve`` and ``ServeSession.tick``)."""

    def __init__(self, config: dict, traffic: dict, flow: dict, data):
        import repro
        super().__init__(config, traffic, flow, data)
        self._init_loop()
        empty = {c: a[:0] for c, a in data.facts[0].items()}
        self.built = flows.build(flow, empty, data.dims)
        self.session = repro.Session(backend=config["backend"],
                                     metadata=None)
        self.serving = self.session.serve(self.built, fuse=config["fuse"])

    def _rows(self, n: int) -> int:
        return self.tick_rows

    def _request(self, n: int):
        r = self.serving.tick(self.columns(n))
        ok = not (r.retries or r.dead_lettered or _faults(r.cache_stats))
        return _host(r.delta), ok

    def close(self) -> None:
        if self.serving is not None:
            self.serving.close()
        self.session = self.built = self.serving = None


FEEDS = {"batch": BatchFeed, "serve": ServeFeed}
LOOPS = {"batch": BatchLoop, "serve": ServeLoop}


def _path(config: dict, traffic: dict) -> str:
    path = config["path"]
    if path not in LOOPS:
        raise ValueError(f"unknown path {path!r}; have {sorted(LOOPS)}")
    if traffic.get("loop") != "closed":
        raise ValueError(f"only closed-loop traffic is generated, not "
                         f"{traffic.get('loop')!r}")
    return path


def make(config: dict, traffic: dict, flow: dict, data):
    """The loop for the configuration's path."""
    return LOOPS[_path(config, traffic)](config, traffic, flow, data)


def feed(config: dict, traffic: dict, flow: dict, data):
    """The feed alone (no program) for the configuration's path."""
    return FEEDS[_path(config, traffic)](config, traffic, flow, data)
