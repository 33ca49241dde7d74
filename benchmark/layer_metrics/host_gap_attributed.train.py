"""Device: the share of the device's idle time, in gaps over 0.5 ms, that
lies under a named program span: the host was doing something the trace can
name. 100 where no gap is that long. In %. Source: host and device planes of
the run's capture on one clock (``benchmark/span_reduce.py``)."""

from benchmark import span_reduce


def read(run: dict):
    out = span_reduce.for_run(run)
    if not out or not out["spans"] or not out["busy_s"]:
        return None
    idle = out["idle"]
    return (100.0 * idle["attributed_s"] / idle["total_s"]
            if idle["total_s"] else 100.0)
