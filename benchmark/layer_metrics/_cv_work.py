"""The bytes a cross-validation's evaluations NEED, and the peak they are
held against, keyed by `device_kind` (Google Cloud documentation, "TPU
v5e": 819 GB/s of HBM bandwidth). A device that is not in the table is an
error.

An evaluation is one grid point's margins over one fold's rows: w . [x 1]
for every row of the expanded float32 block, which the program keeps whole
on the chip and a fold is a mask over. All the grid's points of a fold can
be one (points x (d + 1)) x ((d + 1) x rows) product over ONE read of the
block, so the useful traffic is the block read once a FOLD: folds x rows x
(d + 1) x 4 bytes, whatever the grid's width. The program as it is fits a
point and makes its margins before it fits the next, so it reads the block
once a (point, fold): six reads a fold for the one that is needed, which
the share shows as it should (PERF.md section 5 gives the figure by the
program's own layout beside it). The ranking's own traffic (a sort of the
margins) is no useful byte: what implements it lowers the share."""

PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def eval_bytes(rows: float, slots: int, folds: float) -> float:
    """Bytes the evaluations of `folds` folds need over a block of `rows`
    rows and `slots` = d + 1 float32 columns: the block once a fold."""
    return float(folds) * float(rows) * float(slots) * 4.0


def peak_bytes_per_s(device_kind: str) -> float:
    if device_kind not in PEAK_BYTES_PER_S:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}")
    return PEAK_BYTES_PER_S[device_kind]
