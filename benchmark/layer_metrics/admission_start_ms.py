"""Serving engine: milliseconds of host time a prefill chunk of prompt that
``_start_admission`` spends taking requests up before their first chunk: the
digest walk over the prompt, page leases, the prefix lookup, the fresh working
row. The device holds nothing of the request meanwhile. Summed over the
slice's installs and divided by the chunks they ran, as ``admission_ms`` is,
so it is that reading's part. Source: the ``start_s`` and ``chunks`` fields
of the ``kv_install`` spans in the serving child's capture (inside a capture
the profiler's Python tracer inflates it: PERF.md). Nothing to read where the
program writes no such fields or the slice's installs ran no chunk."""

from benchmark import harness


def read(run: dict):
    return harness.load_named("layer_metrics", "admission_ms").per_chunk_ms(
        run, "start_s")
