"""Data generator `kddcup`: a table in the shape of KDD Cup 1999's
connection records as the k-means|| paper clusters them (Bahmani et al.,
"Scalable K-Means++", VLDB 2012: 4.8 M points of 42 numbers), made from
the seed. The file itself is not in-tree and the chip's machine has no
network, so the rows are drawn from a planted mixture that keeps what
makes the table hard for a clustering in float32:

- 42 `double` columns of KDD99's kinds: three heavy-tailed ones (a
  duration and two byte counts: log-normal, whole numbers, into the
  millions, many exact zeros), fifteen small counts in 0..511, fifteen
  rates in [0, 1] at two decimals, nine 0/1 flags;
- classes as skewed as KDD99's 23 (`_CLASS_SHARES`: two hold about four
  fifths of the rows, a dozen under a thousandth each), each a mixture of
  components of its own with Zipf shares, so that a thousand centers have
  structure to find inside the large classes and the small ones are a few
  thousand rows far from everything.

A component fixes, a column, the log-mean and spread of a heavy column,
the mean of a count, the level of a rate and the odds of a flag; a row is
its component's draw. The rows come in `CHUNKS` independent streams
(`SeedSequence(seed).spawn`), a chunk a thread: the table is the same
whatever the threads do. A generator is a file
`benchmark/data/<name>.py` with `make(params, seed)`; `params` is the
configuration's `data` object (`rows`).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd

HEAVY = ["duration", "src_bytes", "dst_bytes"]
COUNTS = ["hot", "num_failed_logins", "num_compromised", "num_root",
          "num_file_creations", "num_shells", "num_access_files",
          "num_outbound_cmds", "wrong_fragment", "urgent", "count",
          "srv_count", "dst_host_count", "dst_host_srv_count", "service_id"]
RATES = ["serror_rate", "srv_serror_rate", "rerror_rate", "srv_rerror_rate",
         "same_srv_rate", "diff_srv_rate", "srv_diff_host_rate",
         "dst_host_same_srv_rate", "dst_host_diff_srv_rate",
         "dst_host_same_src_port_rate", "dst_host_srv_diff_host_rate",
         "dst_host_serror_rate", "dst_host_srv_serror_rate",
         "dst_host_rerror_rate", "dst_host_srv_rerror_rate"]
FLAGS = ["land", "logged_in", "root_shell", "su_attempted", "is_host_login",
         "is_guest_login", "protocol_tcp", "protocol_udp", "flag_sf"]
COLUMNS = HEAVY + COUNTS + RATES + FLAGS

#: the 23 classes' shares of the rows, KDD99's skew
_CLASS_SHARES = np.array(
    [0.57, 0.22, 0.17, 0.02, 0.008, 0.004, 0.002, 0.0015, 0.001, 0.0008,
     0.0006] + [0.0003] * 4 + [0.0002] * 4 + [0.0001] * 4)
#: components a class is a mixture of: many inside the large ones
_CLASS_COMPONENTS = [160, 120, 240, 40, 24, 16, 12, 8, 8, 6, 6] + [4] * 12
CHUNKS = 8


def _components(rng):
    """Every component's share of the rows and its parameters a column."""
    shares, parts = [], {k: [] for k in (
        "log_mean", "log_sd", "zero", "count", "rate", "flag")}
    for share, many in zip(_CLASS_SHARES / _CLASS_SHARES.sum(),
                           _CLASS_COMPONENTS):
        zipf = 1.0 / np.arange(1, many + 1)
        shares.append(share * zipf / zipf.sum())
        # what the class is about, and how its components differ from it
        base = rng.normal([2.0, 6.0, 6.5], [2.0, 2.5, 3.0])
        parts["log_mean"].append(base + rng.normal(0, 1.2, (many, 3)))
        parts["log_sd"].append(rng.uniform(0.05, 0.6, (many, 3)))
        # a column a class never fills is exactly zero in most of it
        parts["zero"].append(np.clip(
            rng.choice([0.0, 0.9, 1.0], 3, p=[0.5, 0.2, 0.3])
            + rng.normal(0, 0.05, (many, 3)), 0, 1))
        level = rng.choice([0.0, 2.0, 30.0, 250.0, 500.0], 15,
                           p=[0.45, 0.2, 0.15, 0.1, 0.1])
        parts["count"].append(np.clip(
            level * rng.uniform(0.5, 1.1, (many, 15)), 0, 511))
        rate = rng.choice([0.0, 0.05, 0.5, 1.0], 15, p=[0.4, 0.15, 0.15, 0.3])
        parts["rate"].append(np.clip(
            rate + rng.normal(0, 0.08, (many, 15)), 0, 1))
        parts["flag"].append(np.clip(
            rng.choice([0.0, 1.0], 9, p=[0.7, 0.3])
            + rng.choice([0.0, 0.02, -0.02], (many, 9)), 0, 1))
    return np.concatenate(shares), {
        k: np.concatenate(v).astype(np.float32) for k, v in parts.items()}


def _chunk(out: np.ndarray, sequence, shares, parts) -> None:
    """Fills `out`, float64 (42, rows): a column a row of the array."""
    rows = out.shape[1]
    rng = np.random.default_rng(sequence)
    comp = np.searchsorted(np.cumsum(shares), rng.random(rows, np.float32),
                           side="right").clip(0, len(shares) - 1)

    def noise():
        return rng.standard_normal(rows, np.float32)

    at = 0
    for j in range(3):
        value = np.rint(np.exp(parts["log_mean"][comp, j]
                               + parts["log_sd"][comp, j] * noise()))
        value[rng.random(rows, np.float32) < parts["zero"][comp, j]] = 0.0
        out[at] = value
        at += 1
    for j in range(15):
        mean = parts["count"][comp, j]
        out[at] = np.clip(np.rint(mean + np.sqrt(mean) * noise()), 0, 511)
        at += 1
    for j in range(15):
        out[at] = np.round(np.clip(
            parts["rate"][comp, j] + 0.03 * noise(), 0, 1), 2)
        at += 1
    for j in range(9):
        out[at] = rng.random(rows, np.float32) < parts["flag"][comp, j]
        at += 1


def make(params: dict, seed: int) -> pd.DataFrame:
    n = int(params["rows"])
    root = np.random.SeedSequence(int(seed))
    first, *streams = root.spawn(1 + CHUNKS)
    shares, parts = _components(np.random.default_rng(first))
    ends = np.cumsum([n // CHUNKS + (i < n % CHUNKS) for i in range(CHUNKS)])
    table = np.empty((len(COLUMNS), n), np.float64)
    with ThreadPoolExecutor(max_workers=CHUNKS) as pool:
        list(pool.map(
            lambda a: _chunk(table[:, a[0]:a[1]], a[2], shares, parts),
            zip(ends - np.diff(ends, prepend=0), ends, streams)))
    return pd.DataFrame(dict(zip(COLUMNS, table)), copy=False)
