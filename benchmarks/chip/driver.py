"""The one traffic generator: reads a mix file and drives a subject.

A mix (``traffic/<name>.json``) sets:

``session``       ``"fresh"``: every request plans on a fresh copy of the
                  seeded cluster with a fresh planner (cold plans);
                  ``"warm"``: one planner on one cluster for the whole
                  run, converged in set-up (balancer rounds).
``budget``        moves per request, null = plan to convergence.
``growth_share``  user bytes ingested before each request, as a share of
                  the cluster's raw capacity, into one user pool drawn
                  with probability proportional to its stored bytes.
``outage``        ``{"class", "first", "every", "back_after"}``: at tick
                  ``first`` and every ``every`` ticks after it (0 = once)
                  one seeded OSD of ``class`` is marked out and its
                  shards re-placed by a capacity-weighted legal draw;
                  ``back_after`` ticks later it is marked in again.
``warmup``        the same fields for the scratch ticks of set-up
                  (``ticks``, ``outage``), so every program the window
                  runs is compiled before it.
``trace_seconds`` how much of the window the device trace covers.
``check``         ``{"sample", "slowest"}``: warm requests compared with
                  the reference (a seeded sample plus the slowest).

A subject is anything with ``session()`` returning an object with
``plan(budget) -> (seconds, moves)``, ``grow``, ``set_out`` and
``apply``: the program (``program.Program``) or the reference in its
place (``control.RefProgram``).  Mutations are made outside the timed
call.  Every seed gets the same amount of work: the same cluster in
another OSD order, and the same number of growth and outage events.
"""

from __future__ import annotations

import contextlib
import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from benchmarks.chip.reference import RefState

HERE = Path(__file__).resolve().parent


def load_traffic(name: str, directory: Path = HERE / "traffic") -> dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


class Schedule:
    """Seeded per-tick mutations: growth and outages."""

    def __init__(self, dep, growth_share: float, outage: dict | None,
                 seed: int, stream: int):
        self.rng = np.random.default_rng((seed, stream))
        self.growth = growth_share * float(dep.cap.sum())
        user = [p for p in dep.pools if p.is_user_data]
        self.user = [p.id for p in user]
        self.stored = np.array([p.stored_bytes for p in user])
        self.outage = outage
        if outage:
            if outage["every"] and outage["back_after"] >= outage["every"]:
                raise ValueError("an OSD must be back in before the next "
                                 "outage")
            self.candidates = [i for i in range(dep.n_dev)
                               if dep.dev_class[i] == outage["class"]]
        self.out_osd = None
        self.out_tick = -1

    def _outage_at(self, t: int) -> bool:
        o = self.outage
        if not o or t < o["first"]:
            return False
        if o["every"] == 0:
            return t == o["first"]
        return (t - o["first"]) % o["every"] == 0

    def events(self, t: int) -> list[tuple]:
        ev = []
        if self.growth > 0:
            j = int(self.rng.choice(len(self.user),
                                    p=self.stored / self.stored.sum()))
            self.stored[j] += self.growth
            ev.append(("grow", self.user[j], self.growth))
        if self.out_osd is not None \
                and t == self.out_tick + self.outage["back_after"]:
            ev.append(("in", self.out_osd))
            self.out_osd = None
        if self._outage_at(t):
            osd = int(self.rng.choice(self.candidates))
            self.out_osd, self.out_tick = osd, t
            ev.append(("out", osd))
        return ev


def drain(mirror: RefState, osd: int, rng) -> list[tuple]:
    """Mark ``osd`` out and re-place each of its shards, in (PG, slot)
    order, on a capacity-weighted draw among the devices its rule
    accepts: CRUSH's part after an OSD goes out, not the balancer's."""
    mirror.set_out(osd, True)
    moves = []
    rows, slots = mirror.shards_on(osd)
    for r, s in zip(rows.tolist(), slots.tolist()):
        cands = np.flatnonzero(mirror.legal_mask([r], [s])[0])
        if not cands.size:
            continue                    # degraded: no legal destination
        w = mirror.cap[cands].astype(np.float64)
        w /= w.sum()
        dst = int(cands[int(rng.choice(len(cands), p=w))])
        mirror.apply(r, s, osd, dst)
        moves.append((r, s, osd, dst))
    return moves


@dataclass
class Request:
    index: int
    seconds: float
    moves: list
    ops: list = field(default_factory=list)   # mutations before the call
    illegal: int = 0


def _null(_name):
    return contextlib.nullcontext()


class Workload:
    """One cell's traffic on one subject, from its seed."""

    def __init__(self, traffic: dict, dep, seed: int, subject,
                 annotate=None):
        self.t = traffic
        self.dep = dep
        self.seed = seed
        self.subject = subject
        self.annotate = annotate or _null   # host spans in the trace
        self.budget = traffic["budget"]
        self.warm = traffic["session"] == "warm"
        self.session = None
        self.mirror = None          # the benchmark's model of the cluster
        self.start_mirror = None    # the mirror when the window opened
        self.setup_illegal = 0
        self.refused = 0            # re-placements the subject refused

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        if not self.warm:
            # one cold plan compiles every program a cold plan runs
            self.subject.session().plan(self.budget)
            return
        self.session = self.subject.session()
        self.mirror = RefState(self.dep)
        self._settle()
        w = self.t["warmup"]
        sched = Schedule(self.dep, self.t["growth_share"], w["outage"],
                         self.seed, 0x5C2A)
        for t in range(w["ticks"]):
            self._mutate(sched, t)
            _, moves = self.session.plan(self.budget)
            self.setup_illegal += self._apply_checked(moves)
        self._settle()

    def _settle(self) -> None:
        _, moves = self.session.plan(None)
        self.setup_illegal += self._apply_checked(moves)

    # -- mutations and the mirror ------------------------------------------------

    def _mutate(self, sched: Schedule, t: int) -> list[tuple]:
        ops = []
        for ev in sched.events(t):
            if ev[0] == "grow":
                _, pool, nbytes = ev
                self.session.grow(pool, nbytes)
                self.mirror.grow(pool, nbytes)
                ops.append(ev)
            elif ev[0] == "out":
                osd = ev[1]
                self.session.set_out(osd, True)
                rng = np.random.default_rng((self.seed, 0xD5A1, t))
                moves = drain(self.mirror, osd, rng)
                for mv in moves:
                    try:
                        self.session.apply(*mv)
                    except ValueError:
                        # the subject holds the shard elsewhere: its
                        # state left what its answers said
                        self.refused += 1
                ops.append(("out", osd, moves))
            else:
                osd = ev[1]
                self.session.set_out(osd, False)
                self.mirror.set_out(osd, False)
                ops.append(ev)
        return ops

    def _apply_checked(self, moves) -> int:
        """Apply a subject's moves to the mirror; count the illegal."""
        bad = 0
        for row, slot, src, dst in moves:
            if self.mirror.acting[row, slot] != src \
                    or not self.mirror.is_legal(row, slot, dst):
                bad += 1
                continue
            self.mirror.apply(row, slot, src, dst)
        return bad

    # -- the window ----------------------------------------------------------------

    def window(self, seconds: float, on_request=None) -> list[Request]:
        """Requests until ``seconds`` have passed; the last one started
        runs to its end.  ``on_request(requests)`` is called after each
        (the harness stops the device trace from there)."""
        reqs: list[Request] = []
        if self.warm:
            self.start_mirror = self.mirror.copy()
            sched = Schedule(self.dep, self.t["growth_share"],
                             self.t["outage"], self.seed, 0x71C4)
        end = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < end:
            if self.warm:
                with self.annotate("bench.mutate"):
                    ops = self._mutate(sched, i)
                session = self.session
            else:
                ops = []
                with self.annotate("bench.copy"):
                    session = self.subject.session()
            with self.annotate("bench.plan"):
                dt, moves = session.plan(self.budget)
            req = Request(i, dt, moves, ops)
            if self.warm:
                with self.annotate("bench.mutate"):
                    req.illegal = self._apply_checked(moves)
            reqs.append(req)
            if on_request is not None:
                on_request(reqs)
            i += 1
        return reqs
