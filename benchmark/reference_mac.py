"""The plain reference of the MAC cycle detector's verdict: the greatest
closed set of blocked, childless actors, over plain dicts and a worklist.
Nothing is imported from ``uigc_tpu``; the program cannot change this file.

Input: the blocked table ``{actor: (rc, num_children, {target: weight})}``
of the actors whose latest word to the detector is a ``BLK`` (an actor's
map holds its own entry, with its ``RC_INC``), and ``pending``, the actors
in a confirmation that has not been settled.  Actors are any hashable ids.

    C          = {a in table : num_children(a) == 0, a not in pending}
    balance(m) = rc(m) + RC_INC - sum over o in C of w_o(m)
    seeds      = {m in C : balance(m) != 0}
    live       = the least set that holds the seeds and, with o, every
                 m in C with w_o(m) > 0
    G          = C - live

A balance other than 0 is weight that an actor outside ``C`` holds or that
is in flight (a ``DecMsg``, an ``IncMsg``, a ref in a message).  ``G`` is
closed: every holder of a member is a member.
"""

from __future__ import annotations

from typing import Dict, Hashable, Iterable, Mapping, Set, Tuple

#: the weight an actor's own entry starts with and that ``rc`` never counts
#: (upstream uigc-akka MAC.scala:17 and :118-120)
RC_INC = 255

Table = Mapping[Hashable, Tuple[int, int, Mapping[Hashable, int]]]


def candidates(table: Table, pending: Iterable[Hashable] = ()) -> Set[Hashable]:
    waiting = set(pending)
    return {a for a, (_, children, _) in table.items() if children == 0 and a not in waiting}


def balances(table: Table, cands: Set[Hashable]) -> Dict[Hashable, int]:
    balance = {m: table[m][0] + RC_INC for m in cands}
    for owner in cands:
        for target, weight in table[owner][2].items():
            if target in balance:
                balance[target] -= weight
    return balance


def garbage(table: Table, pending: Iterable[Hashable] = ()) -> Set[Hashable]:
    """``G`` of the table: the candidates no seed reaches."""
    cands = candidates(table, pending)
    live = {m for m, b in balances(table, cands).items() if b != 0}
    work = list(live)
    while work:
        owner = work.pop()
        for target, weight in table[owner][2].items():
            if weight > 0 and target in cands and target not in live:
                live.add(target)
                work.append(target)
    return cands - live
