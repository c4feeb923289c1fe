"""``mfu.sharded``: the sharded training step's required work at the
peak of the mesh's chips, as a share of the traced time per step (%),
read as ``mfu.train`` reads it; the work (``bench/work``) is that of the
shared-edge, Markov-link step, over ``chips`` chips."""
import spec

read = spec.load_module("metrics", "mfu.train").read
