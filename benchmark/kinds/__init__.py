"""One driver a kind of traffic, found by the `kind` of the traffic file."""
