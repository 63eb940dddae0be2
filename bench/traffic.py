"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

A mix has any of:

* ``preload_histories``: histories written through the engine in set-up,
  so that queries have memory to read (``history`` gives their shape);
* ``backlog``: a saturated import. Histories are generated lazily, each
  with its own session ids, and the ingest queue is topped up to
  ``min_queued_sessions`` before every engine step;
* ``streams``: open-loop arrivals of ``query``, ``write`` and ``lm``
  requests, each at its ``rate_per_s``. Writes are short sessions
  (``distractor_turns`` chit-chat turns beside their statements) about the
  preloaded users; queries ask about the preloaded history; LM prompts are
  random token ids of log-uniform length in ``prompt_tokens``.

Every seed gets the same amount of work: a stream of rate r over a window
of T seconds has round(r T) arrivals, whose gaps are one fixed set (drawn
from ``shape_seed``) put in an order drawn from the seed, and LM prompt
lengths are likewise one fixed set in a seeded order. Histories take
their shape from ``shape_seed`` and their names and values from the seed
(``workload.history``), so every seed imports the same sessions, turns and
tokens. The seed draws the content: names, values and token ids.

``Plan.planted`` maps the id of every session the plan can send to the
statements planted in it.
"""
from __future__ import annotations

import dataclasses
import math
import random
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import workload


@dataclass
class Req:
    kind: str                    # query | write | lm | session
    due: float                   # seconds after the window opens
    payload: Any
    submitted: Optional[float] = None
    done: Optional[float] = None
    rid: Optional[int] = None
    tokens: List[float] = field(default_factory=list)   # lm: token times
    obj: Any = None              # lm: the engine's Request


def _sub(seed: int, tag: str) -> int:
    return random.Random(f"{seed}:{tag}").getrandbits(63)


def arrivals(n: int, seconds: float, shape_seed: int, seed: int,
             tag: str) -> List[float]:
    """``n`` arrival times in [0, seconds): n + 1 exponential gaps from the
    fixed shape generator, scaled to sum to ``seconds``, in a seeded order."""
    if n <= 0:
        return []
    shape = random.Random(f"{shape_seed}:{tag}")
    gaps = [shape.expovariate(1.0) for _ in range(n + 1)]
    total = sum(gaps)
    gaps = [g * seconds / total for g in gaps]
    random.Random(_sub(seed, tag + ":order")).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps[:n]:
        t += g
        out.append(t)
    return out


def prompt_lengths(n: int, lo: int, hi: int, shape_seed: int, seed: int) -> List[int]:
    shape = random.Random(f"{shape_seed}:lm-lengths")
    lens = [int(round(math.exp(shape.uniform(math.log(lo), math.log(hi)))))
            for _ in range(n)]
    random.Random(_sub(seed, "lm-lengths:order")).shuffle(lens)
    return lens


def history_kw(mix: dict) -> dict:
    h = mix["history"]
    return dict(entities=h["entities"], sessions_per_history=h["sessions"],
                transitions=h["transitions"],
                distractor_turns=h["distractor_turns"])


@dataclass
class Plan:
    preload: List                 # sessions written in set-up
    questions: List               # the pool queries are drawn from
    window: List[Req]             # open-loop requests, by due time
    warm_sessions: Iterator       # sessions written while warming up
    backlog: Optional[Iterator] = None
    planted: Dict[str, list] = field(default_factory=dict)


def histories(shape_seed: int, seed: int, tag: str, planted: Dict[str, list],
              **kw) -> Iterator:
    """Sessions of one generated history after another, without end, each
    history with session ids of its own."""
    i = 0
    while True:
        sess, _, _ = workload.history(
            _sub(shape_seed, f"{tag}{i}"), content_seed=_sub(seed, f"{tag}{i}"),
            prefix=f"{tag}{i}-", planted=planted, **kw)
        yield from sess
        i += 1


def plan(mix: dict, seed: int, seconds: float, vocab_size: int) -> Plan:
    shape_seed = mix.get("shape_seed", 0)
    planted: Dict[str, list] = {}
    preload, pool = [], []
    for i in range(mix.get("preload_histories", 0)):
        sess, qs, _ = workload.history(_sub(shape_seed, f"preload{i}"),
                                       content_seed=_sub(seed, f"preload{i}"),
                                       prefix=f"p{i}-", num_questions=200,
                                       planted=planted, **history_kw(mix))
        preload += sess
        pool += qs
    streams = mix.get("streams", {})
    kw = history_kw(mix)
    if "write" in streams:      # warm writes are as short as the window's
        kw.update(distractor_turns=streams["write"]["distractor_turns"])
    window: List[Req] = []
    rng = random.Random(_sub(seed, "content"))
    if "query" in streams:
        n = round(streams["query"]["rate_per_s"] * seconds)
        for k, t in enumerate(arrivals(n, seconds, shape_seed, seed, "query")):
            window.append(Req("query", t, dataclasses.replace(pool[k % len(pool)])))
    if "write" in streams:
        w = streams["write"]
        n = round(w["rate_per_s"] * seconds)
        kw = dict(history_kw(mix), distractor_turns=w["distractor_turns"],
                  sessions_per_history=max(n, 1))
        sess, _, _ = workload.history(_sub(shape_seed, "writes"),
                                      content_seed=_sub(seed, "writes"),
                                      prefix="x-", planted=planted, **kw)
        for s, t in zip(sess, arrivals(n, seconds, shape_seed, seed, "write")):
            window.append(Req("write", t, s))
    if "lm" in streams:
        lm = streams["lm"]
        n = round(lm["rate_per_s"] * seconds)
        lo, hi = lm["prompt_tokens"]
        lens = prompt_lengths(n, lo, hi, shape_seed, seed)
        for L, t in zip(lens, arrivals(n, seconds, shape_seed, seed, "lm")):
            toks = [rng.randrange(3, vocab_size) for _ in range(L)]
            window.append(Req("lm", t, (toks, lm["max_new_tokens"])))
    window.sort(key=lambda r: r.due)
    return Plan(preload, pool, window, histories(shape_seed, seed, "w", planted, **kw),
                histories(shape_seed, seed, "b", planted, **history_kw(mix))
                if "backlog" in mix else None, planted)
