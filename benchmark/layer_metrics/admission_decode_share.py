"""Serving engine: the share of admissions' wall that was neither the
request's own start nor its own chunks: the time the ONE admission pipeline
stood while the live batch ran its decode calls (or waited for a free slot),
the price of the per-token stall bound. Source: ``admit_wall_s``, ``start_s``
and ``chunk_work_s`` of the ``kv_install`` spans in the serving child's
capture, summed over the slice. Nothing to read where the program writes no
such fields."""

from benchmark import span_reduce


def read(run: dict):
    fields = [e["fields"] for e in span_reduce.events_of(run, "kv_install")
              if "admit_wall_s" in e["fields"]]
    wall = sum(f["admit_wall_s"] for f in fields)
    if not wall:
        return None
    own = sum(f["start_s"] + f["chunk_work_s"] for f in fields)
    return 100.0 * (wall - own) / wall
