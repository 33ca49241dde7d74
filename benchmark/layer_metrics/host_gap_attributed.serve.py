"""Device: the share of the device's idle time, in gaps over 0.5 ms, that
lies under a named program span, in a serving cell. The same reading as
``host_gap_attributed.train``; one name per cell kind, because the cells'
end-to-end metrics differ."""

from benchmark import harness

read = harness.load_named("layer_metrics", "host_gap_attributed.train").read
