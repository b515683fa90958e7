"""The bytes the build of a factorization's normal equations NEEDS, and the
peak they are held against, keyed by `device_kind` (Google Cloud
documentation, "TPU v5e": 819 GB/s of HBM bandwidth). A device that is not
in the table is an error.

A half-step reads, for every rating, the other side's id (4 bytes), the
rating (4) and the factor row that id names (4 x rank), and writes an
entity's rank^2 + rank float32 sums once. Over a fit's 2 x maxIter
half-steps every rating is read twice an alternation (once in each order)
and every user's and every movie's sums are written once an alternation.
Bandwidth-bound: a statistic is one product and one add for 4 bytes
gathered. The statistics themselves (ratings x (rank^2 + rank) float32,
12.5 GB at the cell's size) are no useful byte: a program that writes them
out and reads them back, or that scans them in several passes, lowers the
share, which cannot pass 100 %."""

PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def build_bytes(ratings: float, entities: float, rank: int,
                half_steps: float) -> float:
    """Bytes `half_steps` half-steps of a fit need over `ratings` ratings
    and `entities` = users + movies: every other half-step is the users',
    so a PAIR of them reads every rating twice and writes every entity's
    sums once."""
    pair = 2.0 * float(ratings) * (4.0 + 4.0 + 4.0 * rank) \
        + float(entities) * (rank * rank + rank) * 4.0
    return float(half_steps) / 2.0 * pair


def peak_bytes_per_s(device_kind: str) -> float:
    if device_kind not in PEAK_BYTES_PER_S:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}")
    return PEAK_BYTES_PER_S[device_kind]
