"""``update_ms.sharded``: device time a training step spends under the
program's ``fleet.update`` scope, the TD update (the ``ref`` formulation
on the mesh), in ms a step, averaged over the mesh's chips
(``stage_trace``, as ``update_ms.train``)."""
import spec

read = spec.load_module("metrics", "update_ms.train").read
