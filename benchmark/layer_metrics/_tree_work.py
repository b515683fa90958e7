"""The work a tree's histogram levels NEED, and the peaks it is held
against, keyed by `device_kind` (Google Cloud documentation, "TPU v5e":
197 TFLOP/s in bf16, 819 GB/s of HBM). A device that is not in the table is
an error.

A level of width `w` histograms three statistics (gradient, hessian, count)
of every row into its node's (column, bin) cells. With the subtraction
(`sml.tree.histSubtraction`, the library's default) rows histogram into
LEFT children alone and a right child is its parent less its sibling, so
the level's node columns are `hw` = 1 at the root and w / 2 below it. The
count is of the formulation the chip favours, a one-hot product, stated
here so that the share means one thing whatever implements it:

    operations  2 x (columns x bins) x rows x 3 hw
    bytes       the level's bins at a byte a (row, column), its node
                statistics in bf16 (rows x 3 hw x 2) and its output in
                float32 (columns x bins x 3 hw x 4)

and a level's least time is the larger of operations over the bf16 peak
and bytes over the HBM's. The rows are the rows FITTED, no padding;
columns, bins and depth the configuration's. What an implementation reads
beyond that (a materialized one-hot is columns x bins bytes a row a level,
256 times the bins), the MXU's columns a narrow level leaves empty, the
leaves' statistics, the all-reduce and the scan for the best split are no
useful work of a level: they lower the share, which cannot pass 100 %."""

PEAK_FLOPS = {"TPU v5 lite": 197e12}
PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def level_widths(depth: int):
    """Node columns a level histograms, root first: 1, then the left
    children of the level above, w / 2."""
    return [2 ** max(level - 1, 0) for level in range(int(depth))]


def level_work(rows: float, columns: int, bins: int, hw: int):
    """(operations, bytes) of one level of `hw` node columns."""
    cells = float(columns) * float(bins)
    return (2.0 * cells * float(rows) * 3.0 * hw,
            float(rows) * columns + float(rows) * 3.0 * hw * 2.0
            + cells * 3.0 * hw * 4.0)


def peaks(device_kind: str):
    if device_kind not in PEAK_FLOPS or device_kind not in PEAK_BYTES_PER_S:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}")
    return PEAK_FLOPS[device_kind], PEAK_BYTES_PER_S[device_kind]


def hist_floor_s(rows: float, columns: int, bins: int, depth: int,
                 rounds: float, device_kind: str) -> float:
    """Least seconds the histogram levels of `rounds` trees can take."""
    flops, bandwidth = peaks(device_kind)
    tree = 0.0
    for hw in level_widths(depth):
        operations, nbytes = level_work(rows, columns, bins, hw)
        tree += max(operations / flops, nbytes / bandwidth)
    return float(rounds) * tree
