"""Whether what the timed path served is correct.

Run after the window has closed, the peak memory read and the
deployment's state freed.  Four numbers, each against the limit its
configuration file states:

- ``unanswered``: requests that never got a non-empty answer (limit 0);
- ``exact_mismatch``: EXACT answers that are not, verbatim, the answer
  cached for that very query before its dispatch (limit 0);
- ``route_mismatch``: requests whose route differs from the float32
  reference route over the bank as it stood at their dispatch, counting
  only requests whose reference similarity (or stage-2 confidence) lies
  clear of every boundary by the stated margins (limit 0);
- ``gen_logit_gap``: over a seeded sample of the MISS and TWEAK rows the
  window generated, the longest always in it, the widest gap by which a
  served (greedy) token's logit lies below the best logit of the float32
  reference run over the request's real prompt (the prompt as fed, less
  the padding the program appended) and the tokens served before it.

The route is compared on a seeded sample of at most ``SAMPLE_ROUTES``
requests.  ``control=True`` puts the reference in the program's place one precision
step down, float8 weights and matmul inputs for the language model and
bfloat16 embeddings for the route, and reads the same numbers for what it
would have served, so that the same limits judge it.
"""
from __future__ import annotations

from typing import Dict, List

import ml_dtypes
import numpy as np

from bench import harness
from bench.reference import route as ref_route

SAMPLE_ROWS = 8
SAMPLE_ROUTES = 4096
QUERY_SUFFIX = ref_route.QUERY_SUFFIX


def _real_rows(tokens: np.ndarray) -> List[int]:
    """Rows of a generate call that are not the batch padding (padding
    repeats row 0)."""
    return [0] + [i for i in range(1, len(tokens))
                  if not np.array_equal(tokens[i], tokens[0])]


def unpadded(ids: List[int]) -> List[int]:
    """A fed row without the padding (token 0) appended after its end."""
    n = len(ids)
    while n and ids[n - 1] == ref_route.PAD:
        n -= 1
    return ids[:n]


def generated_rows(gens) -> List[dict]:
    """Each real row the window's generate calls served: the request's
    prompt (shared prefix and the row's own tokens, without padding) and
    the tokens served."""
    rows = []
    for g in gens:
        if g["phase"] == "setup":
            continue
        toks = np.asarray(g["tokens"])
        for i in _real_rows(toks):
            n = int(g["lengths"][i])
            rows.append({"kind": g["kind"],
                         "prompt": list(g["prefix"]) + unpadded(
                             toks[i].tolist()),
                         "served": np.asarray(g["out"][i][:n]).tolist()})
    return rows


def sample_rows(rows: List[dict], seed: int, k: int = SAMPLE_ROWS):
    """The longest row, one of each kind, and seeded others: k in all."""
    if not rows:
        return []
    rng = np.random.default_rng(harness.stream_seed(seed, "check"))
    size = [len(r["prompt"]) + len(r["served"]) for r in rows]
    pick = [int(np.argmax(size))]
    for kind in ("miss", "tweak"):
        idx = [i for i, r in enumerate(rows) if r["kind"] == kind
               and i not in pick]
        if idx and not any(rows[i]["kind"] == kind for i in pick):
            pick.append(int(rng.choice(idx)))
    rest = [i for i in range(len(rows)) if i not in pick]
    extra = rng.choice(len(rest), size=min(k - len(pick), len(rest)),
                       replace=False) if rest else []
    return [rows[i] for i in pick] + [rows[rest[int(j)]] for j in extra]


def logit_gaps(weights, rows, model: dict, family: str,
               control: bool = False) -> Dict:
    """``gen_logit_gap`` of the sampled rows, by the reference module of
    the model's ``family``."""
    ref_lm = harness.family_module(family)
    m = {k: model[k] for k in ref_lm.KEYS}
    seqs = [(r["prompt"], r["served"]) for r in rows]
    targets = None
    if control:
        # at each position of the same prompts and served tokens, the
        # token the float8 forward puts first
        low = ref_lm.served_gaps(weights, seqs, m, precision="fp8")
        targets = [a for _, a in low]
    gaps = ref_lm.served_gaps(weights, seqs, m, targets=targets)
    return {"gen_logit_gap": float(max(g.max() for g, _ in gaps)),
            "tokens": int(sum(len(g) for g, _ in gaps)),
            "argmax_agree": float(np.mean(np.concatenate(
                [a == np.asarray(s if t is None else t)
                 for (_, a), (_, s), t in zip(
                     gaps, seqs, targets or [None] * len(seqs))])))}


def _window_reqs(run):
    return [q for q in run.reqs if q.meta is not None
            and q.dispatch is not None]


def _bank_history(run, pairs):
    """The bank's query texts in insertion order (the prefilled history,
    then each dispatch's MISS queries, warm-up's included) and, per
    dispatch, how many of them it could see."""
    bank = [q for q, _ in pairs]
    visible = []
    for d in run.rec.dispatches:
        visible.append(len(bank))
        seen = set()
        for t, dec in zip(d["texts"], d["decisions"]):
            if dec == ref_route.MISS and t not in seen:
                seen.add(t)
                bank.append(t)
    return bank, visible


def route_check(run, pairs, emb_params, rr, control: bool = False) -> Dict:
    cfg = run.cell.config
    r_cfg, enc = cfg["router"], cfg["router_models"]
    margins = cfg["limits"]["route_margins"]
    reqs = _window_reqs(run)
    if len(reqs) > SAMPLE_ROUTES:
        rng = np.random.default_rng(harness.stream_seed(run.seed, "routes"))
        pick = np.sort(rng.choice(len(reqs), SAMPLE_ROUTES, replace=False))
        reqs = [reqs[i] for i in pick]
    bank, visible = _bank_history(run, pairs)
    texts = sorted(set(bank) | {q.text for q in reqs})
    emb = ref_route.embed(emb_params, [t + QUERY_SUFFIX for t in texts], enc)
    row = {t: i for i, t in enumerate(texts)}
    bank_rows = np.asarray([row[t] for t in bank])
    q_rows = np.asarray([row[q.text] for q in reqs])
    limit = np.asarray([visible[q.dispatch] for q in reqs])
    costs = [q.meta.get("cost") for q in reqs]
    costs = [None if c == r_cfg["default_cost"] else c for c in costs]
    top, top_s = _topk(emb, bank_rows, q_rows, limit, r_cfg["topk"])
    served = [(q.decision, q.meta["sim"], bool(q.meta.get("stage2")))
              for q in reqs]
    if control:
        low = emb.astype(ml_dtypes.bfloat16).astype(np.float32)
        served = _routes(low, bank, bank_rows, q_rows, limit, reqs, costs,
                         rr, r_cfg, enc)
    ref, band = ref_route.decide(top_s[:, 0], costs, r_cfg,
                                 margins["similarity"], margins["exact"])
    mismatch, checked = 0, 0
    if band.any():
        idx = np.nonzero(band)[0]
        verdict, conf = _stage2(rr, reqs, idx, bank, top, top_s, costs,
                                r_cfg, enc)
        for j, i in enumerate(idx):
            if abs(conf[j]) < margins["stage2"]:
                continue
            checked += 1
            dec, _, s2 = served[i]
            mismatch += int(not s2 or dec != verdict[j])
    for i, (dec, _, s2) in enumerate(served):
        if ref[i] < 0:
            continue
        checked += 1
        mismatch += int(dec != ref[i] or (r_cfg["band"] > 0 and s2))
    sims = np.asarray([s for _, s, _ in served], np.float64)
    live = np.isfinite(top_s[:, 0]) & np.isfinite(sims)
    return {"route_mismatch": mismatch, "route_checked": checked,
            "route_requests": len(reqs),
            "sim_max_diff": float(np.abs(sims - top_s[:, 0])[live].max())
            if live.any() else 0.0}


def _stage2(rr, reqs, idx, bank, top, top_s, costs, r_cfg, enc):
    cand = [[bank[j] + QUERY_SUFFIX if np.isfinite(s) else None
             for j, s in zip(top[i], top_s[i])] for i in idx]
    return ref_route.stage2(
        rr[0], [reqs[i].text + QUERY_SUFFIX for i in idx], cand,
        np.where(np.isfinite(top_s[idx]), top_s[idx], -1.0),
        [costs[i] for i in idx], r_cfg, enc)


def _routes(emb, bank, bank_rows, q_rows, limit, reqs, costs, rr, r_cfg,
            enc):
    """(decision, top-1 similarity, stage 2) of each request, as a router
    reading the embeddings ``emb`` would serve them: no margins, the band's
    rows through the reference's second stage."""
    top, top_s = _topk(emb, bank_rows, q_rows, limit, r_cfg["topk"])
    dec, band = ref_route.decide(top_s[:, 0], costs, r_cfg, 0.0, 0.0)
    if band.any():
        idx = np.nonzero(band)[0]
        verdict, _ = _stage2(rr, reqs, idx, bank, top, top_s, costs, r_cfg,
                             enc)
        dec[idx] = verdict
    return [(int(d), float(s), bool(b))
            for d, s, b in zip(dec, top_s[:, 0], band)]


def _topk(emb, bank_rows, q_rows, limit, k, chunk=256):
    """Float64 cosine top-k of each request over the bank rows inserted
    before its dispatch (``limit`` rows); each (query, limit) once."""
    if len(q_rows) == 0:
        return (np.zeros((0, k), np.int64), np.zeros((0, k), np.float64))
    pairs = np.stack([q_rows, limit], 1)
    uniq, back = np.unique(pairs, axis=0, return_inverse=True)
    if len(uniq) < len(pairs):
        top, top_s = _topk(emb, bank_rows, uniq[:, 0], uniq[:, 1], k, chunk)
        back = back.reshape(-1)
        return top[back], top_s[back]
    eb = emb[bank_rows].astype(np.float64)
    cols = np.arange(len(bank_rows))
    tops, top_ss = [], []
    for i in range(0, len(q_rows), chunk):
        s = emb[q_rows[i:i + chunk]].astype(np.float64) @ eb.T
        s[cols[None, :] >= limit[i:i + chunk, None]] = -np.inf
        part = np.argpartition(-s, k - 1, axis=1)[:, :k]
        ps = np.take_along_axis(s, part, axis=1)
        order = np.argsort(-ps, axis=1, kind="stable")
        tops.append(np.take_along_axis(part, order, axis=1))
        top_ss.append(np.take_along_axis(ps, order, axis=1))
    return np.concatenate(tops), np.concatenate(top_ss)


def exact_check(run, pairs) -> Dict:
    cached: Dict[str, set] = {}
    for q, a in pairs:
        cached.setdefault(q, set()).add(a)
    by_dispatch: Dict[int, list] = {}
    for q in _window_reqs(run):
        by_dispatch.setdefault(q.dispatch, []).append(q)
    bad = exact = 0
    for i, d in enumerate(run.rec.dispatches):
        for q in by_dispatch.get(i, ()):
            if q.decision == ref_route.EXACT:
                exact += 1
                bad += int(q.response not in cached.get(q.text, ()))
        # a dispatch's MISS answers are cached for the dispatches after it
        for t, dec, r in zip(d["texts"], d["decisions"], d["responses"]):
            if dec == ref_route.MISS and r:
                cached.setdefault(t, set()).add(r)
    return {"exact_mismatch": bad, "exact_checked": exact}


def verdict(numbers: Dict, limits: Dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
