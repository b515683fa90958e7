"""Data generator `higgs`: a table in the shape of the UCI HIGGS data set as
the XGBoost paper fits it (Chen & Guestrin, KDD 2016, section 6: "Higgs-1M",
the first million of its 11 M simulated collision events, 28 real-valued
columns and a 0/1 label), made from the seed. The file is not in-tree and
the chip's machine has no network, so the events are drawn from a planted
model that keeps what makes the table work for a histogram-tree fit at 256
bins:

- 21 "low-level" `double` columns, the momenta of a lepton, the missing
  energy and four jets: transverse momenta with a heavy right tail (a
  log-normal about 1, as HIGGS scales them), pseudorapidities (a normal cut
  at +-2.5), azimuths (uniform in +-pi) and four b-tags, which take three
  values as HIGGS's do (the only columns with fewer than 256 distinct
  values: every other column fills its 256 bins);
- 7 "high-level" columns, functions of them as the physicists' are: the
  invariant masses of seven sets of the objects (massless four-vectors),
  each over its own typical value;
- the label, Bernoulli of a planted non-linear score: bumps of three masses
  about their resonances, the b-tags of the two jets that make m_bb, the
  angle between the lepton and the missing energy where the lepton is
  hard, a balance of the two leading jets. No additive score of single
  columns reproduces it: a depth-8 tree has interactions to find. The
  score's scale and offset (`_SCALE`, `_OFFSET`) were set once so that the
  positive share is about 0.53, as HIGGS's is, and the area under the ROC
  curve of the planted probability itself, the best any model can reach, is
  0.80-0.85 (0.828 at seed 1; `benchmark/tools_higgs.py ceiling` reads it).

No value is missing. A generator is a file `benchmark/data/<name>.py` with
`make(params, seed)`; `params` is the configuration's `data` object (`rows`).
"""

import numpy as np
import pandas as pd

LOW = ["lepton_pT", "lepton_eta", "lepton_phi",
       "missing_energy_magnitude", "missing_energy_phi"] + [
    f"jet{j}_{part}" for j in (1, 2, 3, 4)
    for part in ("pt", "eta", "phi", "b_tag")]
HIGH = ["m_jj", "m_jjj", "m_lv", "m_jlv", "m_bb", "m_wbb", "m_wwbb"]
COLUMNS = LOW + HIGH
LABEL = "label"

#: a b-tag's three values and their odds, HIGGS's
_B_TAGS = np.array([0.0, 1.0865, 2.1731])
_B_ODDS = np.array([0.50, 0.30, 0.20])
#: what each mass is divided by: its typical value under `_momenta`
_TYPICAL = {"m_jj": 2.2, "m_jjj": 3.9, "m_lv": 1.6, "m_jlv": 3.3,
            "m_bb": 1.9, "m_wbb": 4.6, "m_wwbb": 6.6}
_SCALE, _OFFSET = 2.2, -0.10


def _momenta(rng, n: int):
    """pT, eta, phi of the lepton, the missing energy (eta 0) and the four
    jets, softer in their order, and the jets' b-tags."""
    def pt(median, spread):
        return median * np.exp(spread * rng.standard_normal(n))

    def eta():
        return np.clip(rng.standard_normal(n), -2.5, 2.5)

    def phi():
        return rng.uniform(-np.pi, np.pi, n)

    parts = {"lepton": (pt(0.85, 0.55), eta(), phi()),
             "missing": (pt(0.85, 0.60), np.zeros(n), phi())}
    for j, median in zip((1, 2, 3, 4), (1.0, 0.9, 0.8, 0.7)):
        parts[f"jet{j}"] = (pt(median, 0.45), eta(), phi())
    tags = {f"jet{j}": _B_TAGS[rng.choice(3, n, p=_B_ODDS)]
            for j in (1, 2, 3, 4)}
    return parts, tags


def _mass(parts, names) -> np.ndarray:
    """The invariant mass of massless four-vectors."""
    e = px = py = pz = 0.0
    for name in names:
        p, eta, phi = parts[name]
        e = e + p * np.cosh(eta)
        px = px + p * np.cos(phi)
        py = py + p * np.sin(phi)
        pz = pz + p * np.sinh(eta)
    return np.sqrt(np.maximum(e * e - px * px - py * py - pz * pz, 0.0))


def _bump(x, at, width):
    return np.exp(-0.5 * ((x - at) / width) ** 2)


def score(parts, tags, high) -> np.ndarray:
    """The planted logit of an event being signal."""
    lepton, missing = parts["lepton"], parts["missing"]
    across = np.cos(lepton[2] - missing[2])
    balance = np.abs(np.log(parts["jet1"][0] / parts["jet2"][0]))
    tagged = (tags["jet3"] > 0) & (tags["jet4"] > 0)
    raw = (1.4 * _bump(high["m_bb"], 1.0, 0.22) * np.where(tagged, 1.0, 0.35)
           + 0.9 * _bump(high["m_wwbb"], 0.95, 0.18)
           + 0.7 * _bump(high["m_jlv"], 0.9, 0.25)
           - 0.6 * across * (lepton[0] > 1.0)
           - 0.5 * balance
           + 0.25 * (tags["jet1"] + tags["jet2"] > 2.0)
           - 0.45 * np.abs(high["m_lv"] - 1.0))
    return _SCALE * (raw - 0.62) + _OFFSET


def events(rows: int, seed: int):
    """(columns as a dict of float64 arrays, the planted probability of
    signal, the label)."""
    rng = np.random.default_rng(int(seed))
    parts, tags = _momenta(rng, rows)
    sets = {"m_jj": ("jet1", "jet2"), "m_jjj": ("jet1", "jet2", "jet3"),
            "m_lv": ("lepton", "missing"),
            "m_jlv": ("jet1", "lepton", "missing"),
            "m_bb": ("jet3", "jet4"),
            "m_wbb": ("jet1", "jet2", "jet3", "jet4"),
            "m_wwbb": ("lepton", "missing", "jet1", "jet2", "jet3", "jet4")}
    high = {name: _mass(parts, of) / _TYPICAL[name]
            for name, of in sets.items()}
    table = {"lepton_pT": parts["lepton"][0], "lepton_eta": parts["lepton"][1],
             "lepton_phi": parts["lepton"][2],
             "missing_energy_magnitude": parts["missing"][0],
             "missing_energy_phi": parts["missing"][2]}
    for j in (1, 2, 3, 4):
        p, eta, phi = parts[f"jet{j}"]
        table.update({f"jet{j}_pt": p, f"jet{j}_eta": eta,
                      f"jet{j}_phi": phi, f"jet{j}_b_tag": tags[f"jet{j}"]})
    table.update(high)
    p_signal = 1.0 / (1.0 + np.exp(-score(parts, tags, high)))
    label = (rng.random(rows) < p_signal).astype(np.float64)
    return {c: table[c] for c in COLUMNS}, p_signal, label


def make(params: dict, seed: int) -> pd.DataFrame:
    table, _, label = events(int(params["rows"]), seed)
    table[LABEL] = label
    return pd.DataFrame(table, copy=False)
