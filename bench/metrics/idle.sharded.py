"""``idle.sharded``: the share of the traced training window in which no
op ran on a chip of the fleet mesh (%), averaged over its chips, read as
``idle.train`` reads it."""
import spec

read = spec.load_module("metrics", "idle.train").read
