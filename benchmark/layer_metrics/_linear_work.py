"""The work a logistic fit's Hessian NEEDS, and the peaks it is held
against, keyed by `device_kind` (Google Cloud documentation, "TPU v5e":
197 TFLOP/s in bf16). A device that is not in the table is an error."""

PEAK_FLOPS = {"TPU v5 lite": 197e12}


def hess_flops(rows: float, slots: int, iterations: float) -> float:
    """Floating-point operations of the weighted Gram [X 1]^T W [X 1] over
    `rows` rows and `slots` = d + 1 columns, once a Newton step that moved
    the coefficients: 2 x rows x slots^2 a step (a multiply and an add an
    entry a row). Steps a converged scan still runs, and the extra passes
    of a product emulated at float32 precision, are no useful work."""
    return float(iterations) * 2.0 * float(rows) * float(slots) ** 2


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}")
    return PEAK_FLOPS[device_kind]
