"""Data generator `movielens`: explicit ratings in the shape of GroupLens'
MovieLens tables (`ml-25m`: 25,000,095 ratings, 162,541 users, 59,047 rated
movies), from the seed. The tables themselves are not redistributable
in-tree and the machine with the chip has no network; what a factorization
is sensitive to is drawn here: the lengths of the segments.

- **Who rates how much.** A user's ratings are 20 (`ml-25m` keeps no user
  with fewer) plus a share of the rest that falls as rank^-`user_skew`; the
  busiest rates tens of thousands (32,202 in `ml-25m`), the median user
  under a hundred. A movie's share of the draws falls as
  (rank + `item_offset`)^-`item_skew`: the most-rated movie gets tens of
  thousands (81,491), and the long tail is rated a handful of times or
  once, so an 80 % split never holds every movie. Uniform draws
  (`courseware.make_movielens_dataset`) give every segment the same length
  and hide the segments that span a block's end.
- **Unique (user, movie) pairs.** A user's movies are first drawn by
  popularity; the draws that repeat one (a busy user soon holds every
  popular movie) are drawn again uniformly among all movies until the
  user's count is whole. The counts a user are exact; a movie's follow its
  share.
- **Ids.** `userId` 1..users; `movieId` `items` distinct values of
  [1, `max_item_id`] (209,171 in `ml-25m`), popularity independent of the id.
- **The rating.** A planted model of rank `planted_rank`:
  clip(round((mean + b_u + b_i + u . v + noise) x 2) / 2, 0.5, 5), with
  half-star steps as `ml-25m` has them; `timestamp` seconds of 1995-2019.

`params`: `rows`, `users`, `items`, and optionally `max_item_id`,
`user_skew`, `item_skew`, `item_offset`, `planted_rank`, `signal`, `noise`. Columns
`userId`, `movieId` (int64), `rating` (float64), `timestamp` (int64), rows
in `userId` order as the published file has them.
"""

import numpy as np
import pandas as pd

DEFAULTS = {"max_item_id": 209_171, "user_skew": 1.0, "user_offset": 150.0,
            "item_skew": 2.5, "item_offset": 1200.0, "planted_rank": 6,
            "signal": 0.6, "noise": 0.5}
MIN_RATINGS = 20
_ROUNDS = 3
_CHUNK = 1 << 22


def _shares(n: int, skew: float, offset: float) -> np.ndarray:
    w = (np.arange(1, n + 1, dtype=np.float64) + offset) ** -skew
    return w / w.sum()


def user_counts(rows: int, users: int, items: int, skew: float,
                offset: float, rng) -> np.ndarray:
    """Ratings a user, summing to `rows` exactly: the floor (what the
    table allows: `MIN_RATINGS`, or fewer where `rows` is small) plus the
    skewed share of the rest, no user past a third of the movies; which
    user is the busiest is drawn."""
    floor = min(MIN_RATINGS, rows // users)
    cap = max(items // 3, floor + 1)
    rest = rows - floor * users
    counts = floor + np.minimum(np.floor(
        rest * _shares(users, skew, offset)).astype(np.int64), cap - floor)
    # what the floors and the cap left over, one each to the next in rank
    short = rows - int(counts.sum())
    while short > 0:
        room = np.flatnonzero(counts < cap)[:short]
        counts[room] += 1
        short -= len(room)
    return counts[rng.permutation(users)]


def _drawn(cdf: np.ndarray, n: int, rng) -> np.ndarray:
    return np.searchsorted(cdf, rng.random(n), side="right")


def _pairs(counts: np.ndarray, items: int, item_share: np.ndarray,
           rng) -> np.ndarray:
    """Sorted unique keys user x items + item, `counts[u]` a user. Every
    movie is rated once at least (its one sure rater drawn by activity);
    the rest of a user's movies are drawn by popularity and, where a draw
    repeats a movie the user holds, drawn again: `_ROUNDS` times by
    popularity (three candidates a missing rating, the first new ones
    kept), then uniformly, which only the few busiest users reach."""
    users = len(counts)
    cdf = np.cumsum(item_share)
    cdf[-1] = 1.0
    who = np.cumsum(counts, dtype=np.float64)
    sure = np.minimum(_drawn(who / who[-1], items, rng), users - 1)
    keys = np.sort(sure.astype(np.int64) * items + np.arange(items))
    have = np.bincount(sure, minlength=users)
    extra = np.empty(0, np.int64)
    for attempt in range(10_000):
        need = np.maximum(counts - have, 0)
        if not need.any():
            break
        owner = np.repeat(np.arange(users, dtype=np.int64),
                          need * (1 if attempt == 0 else 3))
        item = _drawn(cdf, len(owner), rng) if attempt <= _ROUNDS \
            else rng.integers(0, items, len(owner))
        new = np.unique(owner * items + item)
        for held in (keys, extra):
            if len(held):
                at = np.minimum(np.searchsorted(held, new), len(held) - 1)
                new = new[held[at] != new]
        # at most need[u] a user: the first of them in the keys' order
        u = new // items
        new = new[np.arange(len(new)) - np.searchsorted(u, u) < need[u]]
        have += np.bincount(new // items, minlength=users)
        if attempt == 0:
            keys = np.sort(np.concatenate([keys, new]))
        else:
            extra = np.union1d(extra, new)
    return np.sort(np.concatenate([keys, extra]))


def make(params: dict, seed: int) -> pd.DataFrame:
    p = dict(DEFAULTS, **params)
    rows, users, items = int(p["rows"]), int(p["users"]), int(p["items"])
    rng = np.random.default_rng([int(seed), 0x4D4C])
    counts = user_counts(rows, users, items, float(p["user_skew"]),
                         float(p["user_offset"]), rng)
    share = _shares(items, float(p["item_skew"]), float(p["item_offset"]))
    keys = _pairs(counts, items, share, rng)
    user, rank_of = keys // items, keys % items

    # popularity rank -> a movie id: `items` distinct ids, in no order of
    # popularity
    movie_ids = np.sort(rng.choice(
        max(int(p["max_item_id"]), items), size=items, replace=False)) + 1
    movie_ids = movie_ids[rng.permutation(items)]

    k = int(p["planted_rank"])
    # u . v has deviation `signal`: k products of two N(0, s^2) draws
    s = np.float32((float(p["signal"]) ** 2 / k) ** 0.25)
    uf = rng.standard_normal((users, k)).astype(np.float32) * s
    vf = rng.standard_normal((items, k)).astype(np.float32) * s
    ub = rng.normal(0.0, 0.35, users).astype(np.float32)
    vb = rng.normal(0.0, 0.45, items).astype(np.float32)
    rating = np.empty(rows, np.float64)
    for lo in range(0, rows, _CHUNK):
        u, v = user[lo:lo + _CHUNK], rank_of[lo:lo + _CHUNK]
        raw = 3.5 + ub[u] + vb[v] + np.einsum("ij,ij->i", uf[u], vf[v]) \
            + rng.standard_normal(len(u), dtype=np.float32) * np.float32(
                p["noise"])
        rating[lo:lo + _CHUNK] = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0)
    return pd.DataFrame({
        "userId": user + 1,
        "movieId": movie_ids[rank_of],
        "rating": rating,
        "timestamp": rng.integers(789_652_009, 1_574_327_703, rows),
    }, copy=False)
