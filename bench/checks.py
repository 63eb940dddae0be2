"""What decides ``correct``: the outputs of the timed path against the plain
references, each number against its limit. The model's reference is that
of the configuration's architecture (``arch``: its ``bench/arch/<arch>.py``);
the memory kernels' is ``reference.py``.

* ``lm_gap``: a seeded sample of the LM requests the window finished, the
  longest among them. The reference runs once over each prompt with its
  served tokens; at each served token, the gap by which the reference's
  logit of that token lies below the reference's best. The widest gap.
* ``encoder_gap``: a seeded sample of the texts the window's encoder calls
  embedded, the longest among them, and a seeded sample of the fact-index
  rows that acknowledged writes left in the memory, each against its
  fact's text; the distance between the served unit embedding and the
  reference's. The widest.
* ``topk_gap``, ``browse_err``, ``refresh_err``: a seeded sample of the
  memory kernels' calls in the window, inputs and outputs as served. For
  the fact and root scans, how far below the reference's k best scores the
  scores of the rows served lie, and how far the served scores are off;
  for browse scoring and tree refresh, the largest error of an output.
* ``applied_missing``: over every write acknowledged in the window, the
  statements the generator planted in it that the memory holds as no live
  fact sourced from that session, and the turns of it that no dialogue
  cell of that session holds, in order. Exact: the limit is 0.
* ``recovered_missing``: the same, in the memory that
  ``DurableMemForest.open`` restores from the run's journal once the
  engine is gone. Exact: the limit is 0.
* ``unflushed``: acknowledged writes with a tree of theirs (their session
  tree, their facts' entity and scene trees) left unrefreshed: still dirty,
  or without a root-index row. Exact: the limit is 0.
* ``unsynced_acks``: acknowledged writes whose ingest batch returned
  without an fsync of the journal. Exact: the limit is 0.

With ``control=True`` the reference in the next lower precision stands in
for the program: float8 for the bfloat16 model, bfloat16 for the float32
kernels. It reads the same numbers, and has to fail.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional

import numpy as np

import reference
from tokenizer import HashTokenizer


def lm_gap(arch, params, cfg: dict, lms: List, rng: random.Random, n: int,
           control: bool = False) -> Optional[dict]:
    done = [r for r in lms if r.done is not None and r.obj is not None
            and r.obj.out_tokens]
    if not done:
        return None
    done.sort(key=lambda r: -(len(r.payload[0]) + len(r.obj.out_tokens)))
    pick = [done[0]] + rng.sample(done[1:], min(n - 1, len(done) - 1))
    worst, count = 0.0, 0
    for r in pick:
        prompt, out = list(r.payload[0]), list(r.obj.out_tokens)
        toks = prompt + out
        ref = arch.forward(params, cfg, toks, len(prompt) - 1, len(out))
        if control:
            low = arch.forward(params, cfg, toks, len(prompt) - 1, len(out), fp8=True)
            out = list(np.argmax(low, axis=-1))
        best = ref.max(axis=-1)
        gaps = best - ref[np.arange(len(out)), out]
        worst = max(worst, float(gaps.max()))
        count += len(out)
    return {"value": worst, "compared": count}


def encoder_gap(arch, params, cfg: dict, samples: List, rng: random.Random, n: int,
                width: int, control: bool = False,
                index_rows: List = ()) -> Optional[dict]:
    rows = [(t, e) for texts, embs in samples for t, e in zip(texts, embs)]
    if not rows:
        return None
    tok = HashTokenizer(cfg["vocab_size"])
    rows.sort(key=lambda te: -len(tok.encode(te[0])))
    pick = [rows[0]] + rng.sample(rows[1:], min(n - 1, len(rows) - 1))
    pick += list(index_rows)
    worst = 0.0
    for text, emb in pick:
        ids = tok.encode(text)[:width] or [0]
        ref = arch.embed(params, cfg, ids, width)
        got = (arch.embed(params, cfg, ids, width, fp8=True) if control
               else np.asarray(emb, np.float64))
        worst = max(worst, float(np.linalg.norm(got - ref)))
    return {"value": worst, "compared": len(pick)}


def _num(x):
    return None if x is None else int(np.asarray(x))


def topk_gap(calls: List, control: bool = False) -> Optional[dict]:
    if not calls:
        return None
    worst, count = 0.0, 0
    for args, kw, out in calls:
        q, keys, k = args[0], args[1], int(args[2])
        normalize = kw.get("normalize", True)
        nv = _num(kw.get("num_valid"))
        ref = reference.topk_scores(q, keys, normalize, nv)
        order = np.argsort(-ref, axis=1, kind="stable")[:, :k]
        best = np.take_along_axis(ref, order, 1)
        if control:
            low = reference.topk_scores(q, keys, normalize, nv, bf16=True)
            idx = np.argsort(-low, axis=1, kind="stable")[:, :k]
            vals = np.take_along_axis(low, idx, 1)
        else:
            vals = np.asarray(out[0], np.float64)
            idx = np.asarray(out[1])
        for row in range(best.shape[0]):
            for r in range(k):
                if not np.isfinite(best[row, r]):
                    continue
                i = int(idx[row, r])
                got = ref[row, i] if 0 <= i < ref.shape[1] else -np.inf
                gap = max(best[row, r] - got, abs(vals[row, r] - best[row, r]))
                worst = max(worst, float(min(gap, 2.0)))
                count += 1
    return {"value": worst, "compared": count}


def browse_err(calls: List, control: bool = False) -> Optional[dict]:
    if not calls:
        return None
    worst, count = 0.0, 0
    for args, _kw, out in calls:
        child, q, mask = args[:3]
        ref = reference.browse_scores(child, q, mask)
        got = (reference.browse_scores(child, q, mask, bf16=True) if control
               else np.asarray(out, np.float64))
        worst = max(worst, float(np.max(np.abs(got - ref))))
        count += ref.size
    return {"value": worst, "compared": count}


def refresh_err(calls: List, control: bool = False) -> Optional[dict]:
    if not calls:
        return None
    worst, count = 0.0, 0
    for args, _kw, out in calls:
        child, mask = args[:2]
        m = np.asarray(mask) > 0
        rows = m.any(axis=1)                    # parents with children
        ref = reference.tree_refresh(child, mask)
        got = (reference.tree_refresh(child, mask, bf16=True) if control
               else np.asarray(out, np.float64))
        if rows.any():
            worst = max(worst, float(np.max(np.abs(got[rows] - ref[rows]))))
        count += int(rows.sum())
    return {"value": worst, "compared": count}


def _key(subject: str, attribute: str, value: str) -> tuple:
    return tuple(" ".join(x.strip().lower().split()) for x in (subject, attribute, value))


def _own_facts(forest) -> Dict[str, set]:
    """Session id -> keys of the live facts that cite it as a source."""
    out: Dict[str, set] = {}
    for f in forest.facts:
        if forest.fact_alive[f.fact_id]:
            for sid, _chunk in f.sources:
                out.setdefault(sid, set()).add(_key(f.subject, f.attribute, f.value))
    return out


def applied_missing(forest, sessions: List, planted: Dict[str, list]) -> Optional[dict]:
    """Planted statements and turns of ``sessions`` that ``forest`` lacks."""
    if not sessions:
        return None
    facts = _own_facts(forest)
    missing = compared = 0
    for s in sessions:
        have = facts.get(s.session_id, set())
        for key in planted[s.session_id]:
            compared += 1
            missing += _key(*key) not in have
        cells = forest.session_registry.get(s.session_id, {}).get("cells", [])
        text = "\n".join(forest.cells[c].text for c in cells)
        pos = 0
        for t in s.turns:
            compared += 1
            j = text.find(t.text, pos)
            if j < 0:
                missing += 1
            else:
                pos = j + len(t.text)
    return {"value": float(missing), "compared": compared}


def unflushed(forest, sessions: List) -> Optional[dict]:
    if not sessions:
        return None
    matrix, _n, _order = forest.root_index()
    bad = 0
    for s in sessions:
        reg = forest.session_registry.get(s.session_id, {"facts": [], "cells": []})
        scopes = {f"session:{s.session_id}"}
        for fid in reg["facts"]:
            scopes.update(sk for sk, _leaf in forest.placement.get(("fact", fid), []))
        for sk in scopes:
            tree = forest.trees.get(sk)
            if (tree is None or sk in forest.dirty_trees
                    or not np.any(matrix[tree.tree_id])):
                bad += 1
                break
    return {"value": float(bad), "compared": len(sessions)}


def index_rows(forest, sessions: List, rng: random.Random, n: int) -> List:
    """(fact text, its fact-index row) for a seeded sample of the live facts
    that ``sessions`` wrote."""
    fids = sorted(fid for s in sessions
                  for fid in forest.session_registry.get(s.session_id, {}).get("facts", [])
                  if forest.fact_alive[fid])
    pick = rng.sample(fids, min(n, len(fids)))
    return [(forest.facts[f].text, np.array(forest.fact_emb[f])) for f in pick]


def unsynced_acks(acked: List, unsynced: List[str]) -> Optional[dict]:
    if not acked:
        return None
    bad = set(unsynced)
    return {"value": float(sum(s.session_id in bad for s in acked)),
            "compared": len(acked)}


def judge(numbers: Dict[str, Optional[dict]], limits: Dict[str, float],
          required: List[str]) -> bool:
    """Every required number was read, and every number read is within its
    limit (a number without a limit fails)."""
    if any(numbers.get(name) is None for name in required):
        return False
    return all(name in limits and v["value"] <= limits[name]
               for name, v in numbers.items() if v is not None)
