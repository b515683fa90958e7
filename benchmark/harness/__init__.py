"""Runner, lookup, statistics, trace reduction, the look for a chip."""
