"""Gateway: median milliseconds a request waited between admission and the
engine taking it up, in an open-loop cell. The same reading as
``queue_ms.closed``; one name per cell kind, because the cells' end-to-end
metrics differ."""

from benchmark import harness

read = harness.load_named("layer_metrics", "queue_ms.closed").read
