"""Serving engine: mean share of the engine's slots that held a request,
sampled every 0.25 s of the window. In %. Source: ``Gateway.stats()``."""


def read(run: dict):
    samples = run.get("occupancy")
    return 100.0 * sum(samples) / len(samples) if samples else None
