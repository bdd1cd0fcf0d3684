"""The comparison that decides ``correct``.

The reference replays the sampled runs of a call (``rows``) with the
decisions the program made in them, as a served model's tokens are scored
by its reference: GMSA's argmin is chaotic at near-ties, so two sound
implementations part ways after the first tie that rounding breaks
differently, and only a replay compares like with like. Every number is a
gap, so 0 is exact agreement:

* ``decision_gap``: the widest distance of a program decision's score above
  the reference's best, in units of that slot's energy term A V mean(e):
  a decision that is not the argmin reads far above rounding;
* ``<entry>_rel``, for every other entry of the digest (``slot_cost``,
  ``slot_energy``, ``slot_recovery``, a run's ``wan_cost``...): the widest
  gap of one slot's (or run's) value against the replay, measured against
  the larger of its own reference value and the median one's; a slot's bill
  is not averaged over the day, so a lower precision shows;
* an entry the reference gives a floor (``floors``) is measured against it
  where that is larger: the backlog against the jobs that have arrived up
  to that slot, since the queues are a running balance of every job that
  passed through them, and their rounding grows with that count, not with
  what is left;
* a run that holds a draw whose count rounding decides (a uniform within
  float32 rounding of its rate's CDF, ``reference.draws``) is replayed once
  more for every count those draws allow, and read by the closest replay:
  a sound program that computes a service rate in another order of float32
  operations draws the neighbouring count there, about one call in fifty on
  a TPU, and a whole job of backlog is no rounding;
* ``replay_gap``: the largest of all the above, the number the cells hold
  to a limit. Sound runs read float32 rounding in every part. A lower
  precision in the cost tables moves the bills; a decision that is not the
  argmin, or a queue update left out, moves the decision gap and the
  backlog by orders of magnitude more. One limit over all parts holds
  each to rounding.

A digest whose entries or shapes differ from what the entry's call returns
(runs lost or duplicated) reads ``inf`` everywhere. A cell compares the numbers its
``bench/limits/<cell>.json`` lists, each against its own limit; the others
are printed for information.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

import reference

#: Entries that are no bill: the program's decisions, which the reference
#: replays and ``decision_gap`` measures, and the replay's own gap and floors.
NOT_BILLS = ("runs", "choice", "fmax", "gap", "floors")
#: At most this many ambiguous draws of one run are tried in every
#: combination of their counts; any further ones keep the reference's count.
MAX_AMBIGUOUS = 8


def _rel(x, ref, floor=0.0, med=None) -> np.ndarray:
    """Per run (the leading axis), the widest gap of ``x`` to ``ref``
    against the larger of the reference value, ``med`` (the median one's
    by default) and ``floor``."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    med = np.median(np.abs(ref)) if med is None else med
    den = np.maximum(np.maximum(np.abs(ref), med), floor)
    den = np.where(den > 0, den, 1.0)
    gap = np.abs(x - ref) / den
    return gap.reshape(gap.shape[0], -1).max(axis=1)


def names(digest_keys) -> list:
    out = ["decision_gap"]
    out += [f"{f}_rel" for f in sorted(digest_keys) if f not in NOT_BILLS]
    return out + ["replay_gap"]


def shapes_ok(digest: dict, want: dict) -> bool:
    """Whether a program digest has exactly the entries and shapes ``want``
    (the entry's ``shapes``) that one whole call returns."""
    return (set(digest) == set(want)
            and all(np.shape(v) == want[k] for k, v in digest.items()))


def _bills(digest: dict) -> list:
    return [f for f in sorted(digest) if f not in NOT_BILLS]


def _per_run(digest: dict, ref: dict, medians: dict | None = None) -> dict:
    """Every gap of :func:`numbers`, per run compared."""
    medians = medians or {}
    gap = np.asarray(ref["gap"], np.float64)
    out = {"decision_gap": gap.reshape(gap.shape[0], -1).max(axis=1)}
    floors = ref.get("floors", {})
    for f in _bills(digest):
        out[f"{f}_rel"] = _rel(digest[f], ref[f], floors.get(f, 0.0), medians.get(f))
    out["replay_gap"] = np.max(np.stack(list(out.values())), axis=0)
    return out


def numbers(digest: dict, ref: dict) -> dict:
    """Every gap between the decisions and bills of ``digest`` and the
    reference's replay ``ref`` of those decisions."""
    return {k: float(np.max(v)) for k, v in _per_run(digest, ref).items()}


def judge(cfg: dict, traffic: dict, scen: dict, drawn: tuple, digest: dict) -> dict:
    """The numbers of one compared call: the reference's replay of the
    decisions of ``digest`` over its draws ``drawn`` (``reference.draws``),
    each run with an ambiguous draw read by its closest replay."""
    arr, mu, ambiguous = drawn
    ref = reference.evaluate(cfg, traffic, scen, arr, mu, forced=digest)
    per_run = _per_run(digest, ref)
    medians = {f: float(np.median(np.abs(np.asarray(ref[f], np.float64))))
               for f in _bills(digest)}
    keep = ["choice", "fmax"] + _bills(digest)
    for s, amb in enumerate(ambiguous):
        amb = amb[:MAX_AMBIGUOUS]
        if not amb:
            continue
        combos = list(itertools.product(*[(None,) + others for _, _, others in amb]))[1:]
        a = np.repeat(arr[s:s + 1], len(combos), axis=0)
        m = np.repeat(mu[s:s + 1], len(combos), axis=0)
        for c, combo in enumerate(combos):
            for (name, at, _), count in zip(amb, combo):
                if count is not None:
                    (a if name == "arr" else m)[(c,) + at] = count
        dig = {k: np.repeat(np.asarray(digest[k])[s:s + 1], len(combos), axis=0)
               for k in keep}
        alt = _per_run(dig, reference.evaluate(cfg, traffic, scen, a, m, forced=dig),
                       medians)
        best = int(np.argmin(alt["replay_gap"]))
        if alt["replay_gap"][best] < per_run["replay_gap"][s]:
            for k in per_run:
                per_run[k][s] = alt[k][best]
    return {k: float(np.max(v)) for k, v in per_run.items()}


def failed(keys) -> dict:
    return {k: math.inf for k in names(keys)}


def worst(per_call: list) -> dict:
    """The largest reading of each number over the calls compared."""
    return {k: max(c[k] for c in per_call) for k in per_call[0]}


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers with a limit."""
    checks = {}
    ok = True
    for name, spec in limits.items():
        v = readings.get(name, math.inf)
        lim = float(spec["limit"])
        checks[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
