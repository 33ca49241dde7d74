"""Serving engine: median milliseconds the host spends on an engine step's
decode call outside the wait for the device: from the end of admission to the
hand-out of the tokens (the mask, the uploads, the sampling tensors, the
block's size, the call itself, the results made arrays) less the block's
``wait_s``. Where ``engine_host_ms`` is a span less its children, this is
timed. Source: the ``decode_host_s`` field of the ``engine_step`` spans that
made a decode call, in the serving child's capture. Nothing to read where the
program writes no such field."""

from benchmark import span_reduce


def host_s(event: dict):
    fields = event["fields"]
    return fields.get("decode_host_s") if fields.get("n_steps", 0) > 0 \
        else None


def read(run: dict):
    s = span_reduce.median_of(run, "engine_step", host_s)
    return None if s is None else 1e3 * s
