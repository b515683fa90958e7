"""The work a Lloyd step NEEDS, and the peak it is held against, keyed by
`device_kind` (Google Cloud documentation, "TPU v5e": 197 TFLOP/s in
bf16). A device that is not in the table is an error.

A step is two products over the rows: every row's distance to every center
(2 x rows x d x k: a multiply and an add a coordinate a pair) and every
cluster's sum as the assignment matrix's product with the rows (2 x rows x
k x d again, as the MXU runs it; a scatter would need rows x d adds alone,
and the count is of the formulation the chip favours, stated here so that
the share means one thing). The rows are the PUBLISHED count: the rows
fitted, no padding; d and k the configuration's. The extra passes of a
product emulated at float32 precision, the contraction's rows the MXU
leaves empty at d = 42, the arg-min, the seeding and the cost pass are no
useful work of a step: they lower the share, which cannot pass 100 %."""

PEAK_FLOPS = {"TPU v5 lite": 197e12}


def lloyd_flops(rows: float, d: int, k: int, iterations: float) -> float:
    """Floating-point operations of `iterations` Lloyd steps over `rows`
    rows of `d` columns against `k` centers: 4 x rows x d x k a step."""
    return float(iterations) * 4.0 * float(rows) * float(d) * float(k)


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}")
    return PEAK_FLOPS[device_kind]
