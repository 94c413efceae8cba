"""The one general traffic generator (stdlib only).

A traffic mix is a data file under ``benchmark/traffic/`` — this module
reads its parameters and turns ``(file, seed)`` into the requests a run
sends.  Adding a mix is adding a file; no code here names a mix, a kind of
question or an endpoint.

Parameters (JSON object):

``loop``            "closed": each client sends its next request when its
                    last one ended (the only loop a cell uses today; an
                    open loop comes back with the cell that needs it,
                    PERF.md §7)
``clients``         number of clients
``lockstep``        the clients send in rounds, each round when every
                    client has the whole of its last answer
``endpoint``        a key of ``client.SENDERS``
``questions``       list of {"kind": <name>, "weight": n}; a kind is the
                    file ``benchmark/questions/<name>.json`` with its
                    ``templates`` ({name} and {drug} filled per patient)
``ramp_requests``   requests each client completes before the window
                    opens (they are set-up)
``warm_requests``   sequential requests sent first, and ``warm_bursts``,
                    sizes of bursts sent after them, so that every shape
                    the mix uses is compiled before anything is timed
``timeout_s``       client-side limit on one request
``trace_s``         seconds of the window a ``--trace 1`` run profiles

Steadiness: every seed gets the SAME multiset of question templates, in an
order drawn from the seed.  Only the order, the patients asked about and
(through the seeded weights) the retrieved chunks differ from seed to
seed.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, Iterator, List, Tuple

from . import client, corpus

_REQUIRED = ("loop", "clients", "endpoint", "questions")
QUESTIONS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "questions"
)


def load(path: str) -> Dict[str, object]:
    """The mix of ``path``, with each kind's templates read in beside it
    (``mix["templates"][kind]``)."""
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    for key in _REQUIRED:
        if key not in mix:
            raise ValueError(f"{path}: traffic file lacks {key!r}")
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    if int(mix["clients"]) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if mix["endpoint"] not in client.SENDERS:
        raise ValueError(f"{path}: no sender for {mix['endpoint']!r}")
    if not mix["questions"]:
        raise ValueError(f"{path}: no kind of question")
    mix["templates"] = {}
    for entry in mix["questions"]:
        with open(os.path.join(QUESTIONS_DIR, entry["kind"] + ".json"),
                  encoding="utf-8") as f:
            mix["templates"][entry["kind"]] = [
                t["text"] for t in json.load(f)["templates"]
            ]
    return mix


def _kinds_cycle(mix: Dict[str, object]) -> List[Tuple[str, int]]:
    """One period of (kind, template) pairs: each kind ``weight`` times
    per template, so any whole number of periods holds the same shares."""
    period: List[Tuple[str, int]] = []
    for entry in mix["questions"]:
        kind = entry["kind"]
        for _ in range(int(entry.get("weight", 1))):
            period.extend(
                (kind, t) for t in range(len(mix["templates"][kind]))
            )
    return period


def questions(
    mix: Dict[str, object], seed: int, n_patients: int, stream: str
) -> Iterator[Tuple[str, str]]:
    """Endless (kind, question text) for one ``stream`` (a client, or the
    warm-up): periods of the template multiset, each shuffled from the
    seed, over patients drawn from the seed."""
    rng = random.Random(f"questions/{seed}/{stream}")
    period = _kinds_cycle(mix)
    while True:
        order = period[:]
        rng.shuffle(order)
        for kind, template in order:
            who = rng.randrange(n_patients)
            yield kind, corpus.question(
                seed, mix["templates"][kind][template], who
            )
