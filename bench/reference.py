"""Plain reference for the served deltas, and the comparison that decides
``correct``.

It imports nothing of the program. It knows the stated semantics of a
served per-window delta (``SessionConfig.theta_mode = "per_window"``):

* level 1: every channel whose spike count in window p is at least θ,
  with that count;
* level k >= 2: every serial episode whose exact non-overlapped count
  (the paper's Algorithm 1, counted from the start of the stream) grew by
  at least θ in window p, with that growth. An occurrence belongs to the
  window in which its last event is consumed, and the program holds back
  each window's trailing group of equal timestamps for the next window,
  so window p's growth is the count over the events before window p's
  last timestamp minus the same for window p - 1.

The program finds level k >= 2 by Apriori over candidates seeded on two
windows' support. The reference checks it from both sides:

* every episode the program reports is counted here from scratch and
  must carry the reference's growth, which must be at least θ;
* every episode that a plain one-window Apriori finds (level 2 over the
  channels frequent in window p, level k over joins of level k - 1's
  frequent episodes) must be reported. Those candidates are a subset of
  the program's, so each of them is due.

Level 2 is counted for all channel pairs at once (``pair_counts``), level 3
and up a level's candidates at a time (``chain_counts``). Both are closed
forms of Algorithm 1; the tests hold them equal to ``a1_counts``, the
sequential Algorithm 1 copied from the paper's pseudocode.
"""

from __future__ import annotations

import numpy as np


def a1_counts(types: np.ndarray, times: np.ndarray, et, tlo, thi,
              frontiers: np.ndarray) -> np.ndarray:
    """Algorithm 1 on one serial episode ``et`` with edges (tlo, thi]:
    the non-overlapped count after the first ``k`` events, for each ``k``
    in ``frontiers`` (ascending). Events of other channels never change the
    machine, so only the episode's own channels are walked."""
    et = [int(x) for x in et]
    tlo = [int(x) for x in tlo]
    thi = [int(x) for x in thi]
    n = len(et)
    out = np.zeros(len(frontiers), np.int64)
    idx = np.nonzero(np.isin(types, et))[0]
    s = [[] for _ in range(n)]
    count, f = 0, 0
    nf = len(frontiers)
    for j, e, t in zip(idx.tolist(), types[idx].tolist(), times[idx].tolist()):
        while f < nf and frontiers[f] <= j:
            out[f] = count
            f += 1
        if f == nf:
            return out
        for i in range(n - 1, -1, -1):  # top-down over levels
            if e != et[i]:
                continue
            if i == 0:
                s[0].append(t)
                continue
            done = False
            for t_prev in reversed(s[i - 1]):  # newest witness first
                if tlo[i - 1] < t - t_prev <= thi[i - 1]:
                    if i == n - 1:
                        count += 1
                        s = [[] for _ in range(n)]
                        done = True
                    else:
                        s[i].append(t)
                    break
            if done:
                break  # a completion consumes the event
    out[f:] = count
    return out


def chain_counts(types: np.ndarray, times: np.ndarray, eps, tlo, thi,
                 frontiers: np.ndarray, pos: dict | None = None) -> np.ndarray:
    """Algorithm 1 on each serial episode of ``eps`` (one length, the same
    edges (tlo, thi]), as ``a1_counts``, in closed form:
    int64[len(eps), len(frontiers)].

    Since the last completion at position r, the machine's level-i list
    holds exactly the events of channel et[i] after r that end a chain of
    witnesses starting at a level-0 event after r. So give each event the
    newest start of such a chain (``q``: its own position at level 0, the
    largest ``q`` of its witnesses above): an event completes the episode
    when its ``q`` exceeds r, and r moves to it. A start lies before its
    event, so every event up to r has ``q`` below r, and the next
    completion is the first event whose running maximum of ``q`` exceeds
    r. Episodes that share a prefix share its ``q``. ``pos`` maps a
    channel to its event positions, where the caller has them."""
    if pos is None:
        pos = {c: np.nonzero(types == c)[0] for c in {x for ep in eps for x in ep}}
    prefixes: dict[tuple, np.ndarray] = {}
    spans: dict[tuple, tuple] = {}

    def witnesses(prev, cur, i):
        """Each event of ``cur`` has as witnesses the events of ``prev``
        a[k]:b[k] (tlo < t - t_prev <= thi)."""
        key = (prev, cur, i)
        if key not in spans:
            t_prev, t_cur = times[pos[prev]], times[pos[cur]].astype(np.int64)
            spans[key] = (np.searchsorted(t_prev, t_cur - int(thi[i - 1]), side="left"),
                          np.searchsorted(t_prev, t_cur - int(tlo[i - 1]), side="left"))
        return spans[key]

    def newest_start(ep):
        if len(ep) == 1:
            return pos[ep[0]]
        q = prefixes.get(ep[:-1])
        if q is None:
            q = prefixes[ep[:-1]] = newest_start(ep[:-1])
        a, b = witnesses(ep[-2], ep[-1], len(ep) - 1)
        if not len(a):
            return a
        # the largest q over each a[k]:b[k], one reduction over all spans
        # (a -1 closes q, so a span that ends there is in range)
        bounds = np.empty(2 * len(a), np.int64)
        bounds[0::2], bounds[1::2] = a, b
        top = np.maximum.reduceat(np.append(q, -1), bounds)[0::2]
        return np.where(b > a, top, -1)

    out = np.zeros((len(eps), len(frontiers)), np.int64)
    for m, ep in enumerate(eps):
        last = pos[ep[-1]]
        if not len(last):
            continue
        top = np.maximum.accumulate(newest_start(tuple(ep)))
        nxt = np.searchsorted(top, last, side="right").tolist()
        done, n = [], len(nxt)
        j = int(np.searchsorted(top, -1, side="right"))
        while j < n:
            done.append(j)
            j = nxt[j]
        out[m] = np.searchsorted(last[done], frontiers, side="left")
    return out


def pair_counts(types: np.ndarray, times: np.ndarray, num_types: int, lo: int,
                hi: int, frontiers: np.ndarray) -> np.ndarray:
    """Algorithm 1 for every 2-node episode a -> b with edge (lo, hi] at
    once: int64[len(frontiers), a, b] counts after the first ``k`` events.

    For two nodes the machine of a -> b completes at an event of channel b
    exactly when some a-event since its last completion lies in
    [t - hi, t - lo). The newest a-event before t - lo decides that, so the
    state of a pair is the position of its last completion."""
    n = len(types)
    last = np.full((n + 1, num_types), -1, np.int64)
    last[np.arange(1, n + 1), types] = np.arange(n)
    last = np.maximum.accumulate(last, axis=0)  # last[k, a]: newest a < k
    q = np.searchsorted(times, times.astype(np.int64) - lo, side="left")
    reset = np.full((num_types, num_types), -1, np.int64)  # [a, b]
    count = np.zeros((num_types, num_types), np.int64)
    out = np.zeros((len(frontiers), num_types, num_types), np.int64)
    f, nf = 0, len(frontiers)
    floor = times.astype(np.int64) - hi
    for j in range(n):
        while f < nf and frontiers[f] <= j:
            out[f] = count
            f += 1
        if f == nf:
            return out
        b = types[j]
        pos = last[q[j]]  # newest a-event with time < t - lo, per a
        ok = (pos > reset[:, b]) & (pos >= 0)
        ok &= times[np.maximum(pos, 0)] >= floor[j]
        count[ok, b] += 1
        reset[ok, b] = j
    out[f:] = count
    return out


def _join(freq: list[tuple]) -> list[tuple]:
    """Suffix-prefix join of frequent k-episodes into (k+1)-candidates."""
    by_prefix: dict[tuple, list[tuple]] = {}
    for ep in freq:
        by_prefix.setdefault(ep[:-1], []).append(ep)
    return sorted({a + b[-1:] for a in freq for b in by_prefix.get(a[1:], ())})


class ArrayReference:
    """Reference deltas of one array's first ``n_windows`` windows."""

    def __init__(self, types, times, num_types: int, bounds, n_windows: int,
                 theta: int, max_level: int, interval: tuple[int, int]):
        self.types = np.asarray(types, np.int32)
        self.times = np.asarray(times, np.int32)
        self.num_types = num_types
        self.bounds = np.asarray(bounds, np.int64)
        self.theta = theta
        self.max_level = max_level
        self.lo, self.hi = interval
        last_t = self.times[self.bounds[1:n_windows + 1] - 1]
        # events consumed through window p: all before its last timestamp
        self.frontiers = np.searchsorted(self.times, last_t, side="left")
        self.n_windows = n_windows
        self._pairs = pair_counts(self.types, self.times, num_types, self.lo,
                                  self.hi, self.frontiers)
        self._pos = {c: np.nonzero(self.types == c)[0] for c in range(num_types)}
        self._memo: dict[tuple, np.ndarray] = {}

    def _count(self, eps: list[tuple]) -> None:
        """Count the episodes of ``eps`` (one length, 3 or more) that are
        not counted yet, in one batch."""
        todo = [ep for ep in eps if ep not in self._memo]
        if todo:
            k = len(todo[0]) - 1
            counts = chain_counts(self.types, self.times, todo, [self.lo] * k,
                                  [self.hi] * k, self.frontiers, self._pos)
            self._memo.update(zip(todo, counts))

    def growth(self, ep: tuple, p: int) -> int:
        """Growth of episode ``ep`` (channel tuple) in window ``p``."""
        if len(ep) == 1:
            lo, hi = self.bounds[p], self.bounds[p + 1]
            return int(np.count_nonzero(self.types[lo:hi] == ep[0]))
        if len(ep) == 2:
            c = self._pairs[:, ep[0], ep[1]]
        else:
            self._count([ep])
            c = self._memo[ep]
        return int(c[p] - (c[p - 1] if p else 0))

    def due(self, p: int) -> dict[tuple, int]:
        """Episodes a one-window Apriori finds frequent in window ``p``,
        with their growth."""
        lo, hi = self.bounds[p], self.bounds[p + 1]
        hist = np.bincount(self.types[lo:hi], minlength=self.num_types)
        out = {(int(a),): int(hist[a]) for a in np.nonzero(hist >= self.theta)[0]}
        freq1 = np.nonzero(hist >= self.theta)[0]
        d2 = self._pairs[p] - (self._pairs[p - 1] if p else 0)
        sub = d2[np.ix_(freq1, freq1)] >= self.theta
        level = [(int(freq1[i]), int(freq1[j])) for i, j in zip(*np.nonzero(sub))]
        for ep in level:
            out[ep] = int(d2[ep])
        k = 2
        while level and k < self.max_level:
            k += 1
            nxt = []
            cands = _join(level)
            self._count(cands)
            for ep in cands:
                g = self.growth(ep, p)
                if g >= self.theta:
                    out[ep] = g
                    nxt.append(ep)
            level = nxt
        return out


def compare(ref: ArrayReference, served: dict[int, dict]) -> tuple[dict, set]:
    """Fault counts of one array's served deltas (``window_idx`` ->
    ``{"n_events", "episodes"}``) against the reference over the windows
    ``0 .. ref.n_windows - 1``, each due exactly once; and the windows at
    fault."""
    faults = {"missing_windows": 0, "extra_windows": 0, "event_mismatches": 0,
              "count_mismatches": 0, "missed_episodes": 0}
    faults["extra_windows"] = sum(1 for p in served if not 0 <= p < ref.n_windows)
    bad = set()
    for p in range(ref.n_windows):
        d = served.get(p)
        if d is None:
            faults["missing_windows"] += 1
            bad.add(p)
            continue
        before = dict(faults)
        if d["n_events"] != int(ref.bounds[p + 1] - ref.bounds[p]):
            faults["event_mismatches"] += 1
        got = {}
        for et, c in d["episodes"]:
            ep = tuple(int(x) for x in et)
            if ep in got or len(ep) > ref.max_level:
                faults["count_mismatches"] += 1
            got[ep] = int(c)
        for ep, c in got.items():
            g = ref.growth(ep, p)
            if c != g or g < ref.theta:
                faults["count_mismatches"] += 1
        faults["missed_episodes"] += sum(1 for ep in ref.due(p) if ep not in got)
        if faults != before:
            bad.add(p)
    return faults, bad
