"""Serving engine: milliseconds of the ONE admission pipeline's wall a prefill
chunk of prompt: from the pipeline taking a request up to the start of its
install (its digest walk and page leases, its chunks, and the time it stood
while the live batch ran decode calls), summed over the slice's installs and
divided by the chunks they ran, so a slice that holds one install of a long
prompt reads like one that holds thirty of short ones. Source: the
``admit_wall_s`` and ``chunks`` fields of the ``kv_install`` spans in the
serving child's capture. Nothing to read where the program writes no such
fields or the slice's installs ran no chunk."""

from benchmark import span_reduce


def per_chunk_ms(run: dict, field: str):
    """The slice's sum of a ``kv_install`` field over its sum of chunks."""
    fields = [e["fields"] for e in span_reduce.events_of(run, "kv_install")
              if field in e["fields"] and "chunks" in e["fields"]]
    chunks = sum(f["chunks"] for f in fields)
    return 1e3 * sum(f[field] for f in fields) / chunks if chunks else None


def read(run: dict):
    return per_chunk_ms(run, "admit_wall_s")
