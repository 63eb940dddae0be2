"""LongMemEval-S-shaped histories and questions, generated from a seed.

A copy of the program's synthetic generator (``repro.data.synthetic``
``make_workload`` and the statement grammar of ``repro.data.templates``),
kept here so that the traffic the benchmark sends cannot change when the
program does. Each entity has state trajectories (residence, job, project,
preference); their transitions are rendered into statements, interleaved
with chit-chat turns and assistant acknowledgements, and split into
time-ordered sessions. Questions ask for the current, earlier, first state
or the time of a transition.

A history's shape (which attributes, how many transitions and when, where
the statements fall among the chit-chat) comes from one seed, and its
content (who and what: names and values) from another, through a
one-to-one renaming that keeps every name and value at its number of
words. So histories of one shape have the same sessions, turns and tokens
whatever their content seed. The statements planted in each session are
recorded, for the comparison that checks they were written.

Only the plain request types (``Session``, ``Turn``, ``Query``) come from
the program: they are the interface the requests are submitted through.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from repro.core.types import Query, Session, Turn

NAMES = [
    "Bob", "Alice", "Carol", "David", "Erin", "Frank", "Grace", "Henry",
    "Irene", "Jack", "Karen", "Liam", "Mona", "Nina", "Oscar", "Paula",
]
CITIES = [
    "Boston", "Davis", "Miami", "Seattle", "Austin", "Denver", "Chicago",
    "Portland", "Atlanta", "Phoenix", "Madison", "Raleigh",
]
JOBS = [
    "teacher", "nurse", "barista", "carpenter", "designer", "writer",
    "chef", "gardener", "translator", "photographer",
]
PROJECTS = ["Apollo", "Borealis", "Cascade", "Dynamo", "Ember", "Falcon", "Gyro"]
PREFS = ["green tea", "black coffee", "jazz music", "rock climbing",
         "oil painting", "chess", "cycling", "pottery"]
VALUE_POOLS = {"residence": CITIES, "job": JOBS, "project": PROJECTS,
               "preference": PREFS}

MONTHS = ["January", "February", "March", "April", "May", "June", "July",
          "August", "September", "October", "November", "December"]

ATTRS = {
    "residence": {
        "transition": "{subj} moved from {old} to {new} in {date}.",
        "state": "{subj} lives in {val} as of {date}.",
        "q_current": "Where does {subj} live now?",
        "q_before": "Where did {subj} live before moving to {anchor}?",
        "q_when": "When did {subj} move to {anchor}?",
        "q_first": "What was the first place {subj} lived in?",
    },
    "job": {
        "transition": "{subj} changed jobs from {old} to {new} in {date}.",
        "state": "{subj} works as a {val} as of {date}.",
        "q_current": "What does {subj} work as now?",
        "q_before": "What job did {subj} have before becoming {anchor}?",
        "q_when": "When did {subj} become {anchor}?",
        "q_first": "What was the first job {subj} had?",
    },
    "project": {
        "transition": "{subj} switched project {old} to project {new} in {date}.",
        "state": "{subj} is working on project {val} as of {date}.",
        "q_current": "Which project is {subj} working on now?",
        "q_before": "Which project did {subj} work on before project {anchor}?",
        "q_when": "When did {subj} switch to project {anchor}?",
        "q_first": "What was the first project {subj} worked on?",
    },
    "preference": {
        "transition": "{subj} now prefers {new} over {old} since {date}.",
        "state": "{subj}'s favorite thing is {val} as of {date}.",
        "q_current": "What does {subj} prefer now?",
        "q_before": "What did {subj} prefer before {anchor}?",
        "q_when": "When did {subj} start preferring {anchor}?",
        "q_first": "What did {subj} prefer first?",
    },
}

CHITCHAT = [
    "The weather has been quite nice lately.",
    "Did you watch the game last weekend?",
    "I should really get more sleep these days.",
    "Traffic was terrible this morning.",
    "That restaurant downtown finally reopened.",
    "My phone battery keeps dying too fast.",
    "The new season of that show just dropped.",
    "I keep forgetting to water the plants.",
    "Someone recommended a great podcast to me.",
    "The coffee machine at work broke again.",
]
ASSISTANT_ACKS = [
    "That's great to hear, thanks for sharing.",
    "Noted — I'll remember that.",
    "Interesting, tell me more sometime.",
    "Got it, thanks for the update.",
    "Understood, I've made a note of that.",
]


def ts_to_date(ts: float) -> str:
    m = int(ts)
    return f"{MONTHS[m % 12]} {2020 + m // 12}"


@dataclass
class Trajectory:
    subject: str
    attribute: str
    events: List[Tuple[float, str]]      # (ts, value); the first is the start


def trajectories(rng: random.Random, num_entities: int,
                 transitions: int) -> List[Trajectory]:
    out = []
    for subj in rng.sample(NAMES, num_entities):
        for attr, pool in VALUE_POOLS.items():
            if rng.random() < 0.35 and attr != "residence":
                continue                  # not every entity has every attribute
            vals = rng.sample(pool, min(1 + transitions, len(pool)))
            t = rng.uniform(0, 12)
            events = [(t, vals[0])]
            for v in vals[1:]:
                t += rng.uniform(3, 14)
                events.append((t, v))
            out.append(Trajectory(subj, attr, events))
    return out


def renaming(rng: random.Random) -> Dict[str, str]:
    """A one-to-one renaming of the names and of each pool's values, each
    onto one with as many words."""
    out = {}
    for pool in [NAMES] + list(VALUE_POOLS.values()):
        by_words: Dict[int, List[str]] = {}
        for v in pool:
            by_words.setdefault(len(v.split()), []).append(v)
        for group in by_words.values():
            shuffled = list(group)
            rng.shuffle(shuffled)
            out.update(zip(group, shuffled))
    return out


def renamed(trajs: List[Trajectory], names: Dict[str, str]) -> List[Trajectory]:
    return [Trajectory(names[tr.subject], tr.attribute,
                       [(ts, names[v]) for ts, v in tr.events]) for tr in trajs]


def sessions(rng: random.Random, trajs: List[Trajectory], num_sessions: int,
             distractor_turns: int, prefix: str,
             planted: Optional[Dict[str, list]] = None) -> List[Session]:
    """The trajectories' events rendered into ``num_sessions`` time-ordered
    sessions, each with ``distractor_turns`` chit-chat turns besides its
    statements, and every user turn answered by the assistant. Where
    ``planted`` is given, it maps each session id to the (subject,
    attribute, value) of every statement in the session."""
    events = sorted(((ts, tr, i) for tr in trajs
                     for i, (ts, _) in enumerate(tr.events)),
                    key=lambda e: e[0])
    t_min, t_max = events[0][0], events[-1][0] + 1
    bounds = [t_min + (t_max - t_min) * i / num_sessions
              for i in range(num_sessions + 1)]
    out = []
    for s in range(num_sessions):
        lo, hi = bounds[s], bounds[s + 1]
        stmts, keys = [], []
        for ts, tr, i in events:
            if not lo <= ts < hi:
                continue
            keys.append((tr.subject, tr.attribute, tr.events[i][1]))
            g = ATTRS[tr.attribute]
            if i == 0:
                text = g["state"].format(subj=tr.subject, val=tr.events[0][1],
                                         date=ts_to_date(ts))
            else:
                text = g["transition"].format(
                    subj=tr.subject, old=tr.events[i - 1][1],
                    new=tr.events[i][1], date=ts_to_date(ts))
            stmts.append((ts, text))
        n_turns = len(stmts) + distractor_turns
        stmt_iter = iter(sorted(stmts))
        positions = sorted(rng.sample(range(n_turns), len(stmts)))
        turns, tid = [], 0
        for j in range(n_turns):
            if positions and j == positions[0]:
                positions.pop(0)
                ts, text = next(stmt_iter)
            else:
                ts, text = lo + j * 0.01, rng.choice(CHITCHAT)
            turns.append(Turn("user", text, ts, tid))
            turns.append(Turn("assistant", rng.choice(ASSISTANT_ACKS),
                              ts + 0.001, tid + 1))
            tid += 2
        turns.sort(key=lambda t: t.ts)
        sid = f"{prefix}s{s:03d}"
        out.append(Session(sid, turns, ts=lo))
        if planted is not None:
            planted[sid] = keys
    return out


def questions(rng: random.Random, trajs: List[Trajectory],
              num: int) -> List[Query]:
    """Questions in the generator's five categories, in rotation, about
    trajectories with at least two transitions."""
    multi = [tr for tr in trajs if len(tr.events) >= 3]
    rng.shuffle(multi)
    out: List[Query] = []
    kinds = ["current", "historical", "transition_time", "multi_session",
             "single_session"]
    while len(out) < num and multi:
        tr = multi[len(out) % len(multi)]
        g = ATTRS[tr.attribute]
        kind = kinds[len(out) % 5]
        mid = max(1, len(tr.events) - 1)
        subj = tr.subject
        if kind == "current":
            q = Query(g["q_current"].format(subj=subj), kind, subj,
                      tr.attribute, gold=tr.events[-1][1])
        elif kind == "historical":
            anchor = tr.events[mid][1]
            q = Query(g["q_before"].format(subj=subj, anchor=anchor), kind,
                      subj, tr.attribute, anchor_value=anchor,
                      gold=tr.events[mid - 1][1])
        elif kind == "transition_time":
            anchor = tr.events[mid][1]
            q = Query(g["q_when"].format(subj=subj, anchor=anchor), kind,
                      subj, tr.attribute, anchor_value=anchor,
                      gold=ts_to_date(tr.events[mid][0]))
        else:
            q = Query(g["q_first"].format(subj=subj), kind, subj,
                      tr.attribute, gold=tr.events[0][1])
        out.append(q)
    return out


def history(seed: int, *, entities: int, sessions_per_history: int,
            transitions: int, distractor_turns: int, prefix: str,
            num_questions: int = 0, content_seed: Optional[int] = None,
            planted: Optional[Dict[str, list]] = None):
    """One user's history (and questions about it): its shape from ``seed``,
    its names and values from ``content_seed`` (the shape's own where not
    given)."""
    rng = random.Random(seed)
    trajs = trajectories(rng, entities, transitions)
    if content_seed is not None:
        trajs = renamed(trajs, renaming(random.Random(content_seed)))
    sess = sessions(rng, trajs, sessions_per_history, distractor_turns, prefix,
                    planted)
    return sess, questions(rng, trajs, num_questions), trajs
