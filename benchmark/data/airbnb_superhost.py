"""Data generator `airbnb_superhost`: the `airbnb` listings as MLE 03 -
Logistic Regression Lab reads them, with a label the listing's own columns
predict.

The `airbnb` generator's draws in its order (its file is loaded, not
copied), then the table as the course's cleaned set leaves it: the three
columns `airbnb` sprinkles with missing values filled with their medians
(ML 01's imputation; no NaN is left), `host_is_superhost` taken out, and
`label` (1.0 = superhost) drawn from a logistic model of the review scores,
the number of reviews, the host's listings count, `instant_bookable` and
the room type. `airbnb` draws `host_is_superhost` independently of every
column, a label nothing predicts; this one has a true model whose holdout
AUROC is 0.70-0.80. Its coefficients come from the seed, by a generator of
their own, so that the listings are `airbnb`'s to the bit whatever is drawn
here. `params`: `rows`.
"""

import importlib.util
import os

import numpy as np
import pandas as pd

#: the columns the true model reads, with the sign of each one's effect
EFFECTS = (("review_scores_rating", 1.0), ("review_scores_accuracy", 1.0),
           ("review_scores_cleanliness", 1.0), ("review_scores_checkin", 1.0),
           ("review_scores_communication", 1.0),
           ("review_scores_location", 1.0), ("review_scores_value", 1.0),
           ("number_of_reviews", 1.0), ("host_total_listings_count", -1.0))
#: deviation of the true margin about its intercept, and the intercept
#: (about 28 % superhosts; the course's table has about that share)
MARGIN_SD, INTERCEPT = 1.15, -1.2


def _airbnb():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "airbnb.py")
    spec = importlib.util.spec_from_file_location("bench_data_airbnb", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def true_model(pdf: pd.DataFrame, seed: int):
    """(slopes, margin): the true model's slope a column of `EFFECTS` and
    an indicator (`instant_bookable=t`, `room_type=<value>`), and its
    margin a listing. A weight a term is drawn from the seed (0.5-1.5
    times the term's sign) on the column's z-score or on the indicator,
    and the whole is scaled to a deviation of `MARGIN_SD` about
    `INTERCEPT`."""
    rng = np.random.default_rng([int(seed), 0x4D4C4533])   # "MLE3"
    slopes, eta = {}, np.zeros(len(pdf))
    for col, sign in EFFECTS:
        v = pdf[col].to_numpy(dtype=np.float64)
        slopes[col] = sign * rng.uniform(0.5, 1.5) / v.std()
        eta += slopes[col] * v
    room = pdf["room_type"].to_numpy(dtype=object)
    for name, on, sign in (
            ("instant_bookable=t",
             pdf["instant_bookable"].to_numpy(dtype=object) == "t", 1.0),
            ("room_type=Entire home/apt", room == "Entire home/apt", 1.0),
            ("room_type=Shared room", room == "Shared room", -1.0)):
        slopes[name] = sign * rng.uniform(0.5, 1.5)
        eta += slopes[name] * on
    scale = MARGIN_SD / eta.std()
    return ({k: v * scale for k, v in slopes.items()},
            INTERCEPT + scale * (eta - eta.mean()))


def make(params: dict, seed: int) -> pd.DataFrame:
    pdf = _airbnb().make(params, seed)
    for c in ("bedrooms", "bathrooms", "review_scores_rating"):
        pdf[c] = pdf[c].fillna(pdf[c].median())
    _, eta = true_model(pdf, seed)
    rng = np.random.default_rng([int(seed), 0x4C424C])      # "LBL"
    pdf["label"] = (rng.random(len(pdf))
                    < 1.0 / (1.0 + np.exp(-eta))).astype(np.float64)
    return pdf.drop(columns=["host_is_superhost"])
