"""Bytes of the histogram operand a timed fit's dispatches stored on the
device: the window's `tree.operand.bytes` over its fits (columns x bins x
the rows the bin matrix was staged at, padding included, in the type the
operand is stored in: one byte an element on the chip). A program that
does not count it gives nothing to read."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "tree.operand.bytes" not in run.counters_end:
        return None
    return run.counter_delta("tree.operand.bytes") / fits
