"""Shared serving helpers for the recommender templates."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from predictionio_tpu.ingest import BiMap
from predictionio_tpu.ops.topk import build_mask


def score_and_rank(vecs: np.ndarray, item_emb: np.ndarray,
                   items: BiMap, live: Sequence[tuple], plan=None):
    """The shared scoring tail of the catalog recommenders (ALS
    recommendation, two-tower, seqrec): white/black lists to a filter,
    one top-k over the catalog, ItemScore assembly. `live` is
    [(original_index, query, ...)] — only index and query are read.
    With no whitelist in the batch the filters go as ban-index lists —
    the filter is then built ON DEVICE, so big catalogs do not
    re-upload a dense mask per batch — through `plan` (the algorithm's
    warmed serve plan, if it has one) for the rows that fit it
    (`ops/topk.score_banned`); a whitelist needs the dense mask.
    Stages of the batch cycle (obs/trace.stage): `lookup` (ids to
    indexes), the top-k call's own pack, launch and fetch, `unpack`.
    Returns [(original_index, PredictedResult)]."""
    from predictionio_tpu.models.recommendation import (
        ItemScore, PredictedResult,
    )
    from predictionio_tpu.obs import trace
    from predictionio_tpu.ops.topk import NEG_INF, score_banned, topk_scores

    queries = [entry[1] for entry in live]
    vecs = np.asarray(vecs, np.float32)
    with trace.stage("lookup"):
        n_items = item_emb.shape[0]
        ks = [min(q.num, n_items) for q in queries]
        banned = mask = None
        if all(q.whiteList is None for q in queries):
            banned = [[ix for b in (q.blackList or ())
                       if (ix := items.get(b)) is not None]
                      for q in queries]
        else:
            mask = np.concatenate(
                [resolve_item_mask(items, white_list=q.whiteList,
                                   black_list=q.blackList or ())
                 for q in queries], axis=0)
    if mask is None:
        scores, ixs = score_banned(plan, vecs, item_emb, banned, ks)
    else:
        scores, ixs = topk_scores(vecs, item_emb, mask, k=max(ks))
    out = []
    with trace.stage("unpack"):
        scores, ixs = np.asarray(scores), np.asarray(ixs)
        for row, (entry, q) in enumerate(zip(live, queries)):
            found = [ItemScore(items.inverse(int(ix)), float(s))
                     for s, ix in zip(scores[row], ixs[row])
                     if s > NEG_INF / 2][:q.num]
            out.append((entry[0], PredictedResult(tuple(found))))
    return out


def resolve_item_mask(items: BiMap,
                      item_categories: Optional[Dict[str, List[str]]] = None,
                      *,
                      categories: Optional[Sequence[str]] = None,
                      white_list: Optional[Sequence[str]] = None,
                      black_list: Sequence[str] = (),
                      extra_blacklist_ix: Sequence[int] = ()) -> np.ndarray:
    """One [1, n_items] allowed-mask from the standard template filters:
    whiteList / blackList (item ids; unknown ids ignored), extra blacklist
    indexes (seen/unavailable/query items), and a categories any-of filter
    over per-item category lists. Used by the recommendation,
    similarproduct, e-commerce, and two-tower templates."""
    n = len(items)
    white = None
    if white_list is not None:
        white = [ix for it in white_list if (ix := items.get(it)) is not None]
    black = [ix for it in black_list if (ix := items.get(it)) is not None]
    black += list(extra_blacklist_ix)
    mask = build_mask(n, blacklist_ix=black, whitelist_ix=white).copy()
    if categories is not None:
        want = set(categories)
        cat_ok = np.zeros(n, bool)
        for item_id, cats in (item_categories or {}).items():
            ix = items.get(item_id)
            if ix is not None and want & set(cats):
                cat_ok[ix] = True
        mask &= cat_ok[None, :]
    return mask
