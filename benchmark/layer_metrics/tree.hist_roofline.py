"""Share of the chip's roofline that the USEFUL work of a fit's histogram
levels is, over their device time: `_tree_work.hist_floor_s` (a level's
one-hot product against the bf16 peak or its bins, node statistics and
output against the HBM's, the larger, summed over the levels of every
round) over `fit.device.hist_s` (own seconds under `tree.hist`). It counts
the same work whatever implements it. The materialized one-hot of today
cannot read over about a third: its seven shallow levels each stream the
whole operand (columns x bins bytes a row: 6.1 GB at 28 x 256 x 852 k) to
fill 3 to 96 of the MXU's columns, 8 ms a level at the HBM's rate where
the product itself needs 0.2 to 6 ms; only the deepest level (hw = 64, 192
columns, 11.9 ms of product) is bound by the MXU. A build that never
stores the one-hot (ROADMAP S3 b) is what can raise it; it cannot pass
100 %. Bound by the MXU at every level in this count: 2 x columns x bins x
3 hw operations a row against (columns + 6 hw) bytes."""

from benchmark.layer_metrics import _fit_scopes, _tree_work


def read(run):
    seconds = _fit_scopes.seconds_per_fit(run, "tree.hist")
    fits, rounds, depth, columns, bins = (run.facts.get(key) for key in (
        "fits", "tree_rounds", "tree_depth", "tree_columns", "tree_bins"))
    if not seconds or not fits or not rounds or not depth or not columns \
            or not bins:
        return None
    floor = _tree_work.hist_floor_s(
        sum(run.facts["fit_rows"]) / fits, columns, bins, depth, rounds,
        run.device["kind"])
    return 100.0 * floor / seconds
