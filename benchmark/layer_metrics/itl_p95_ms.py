"""Serving engine: 95th percentile of the gaps between a request's output
tokens, over all requests of the window (a decode block delivers its tokens
in a burst, so most gaps are near 0 and the tail is the block time).
Source: ``GatewayResult.token_times``."""


def read(run: dict):
    gaps = sorted(g for r in run.get("rows", []) for g in r["gaps_ms"])
    return gaps[min(len(gaps) - 1, int(0.95 * len(gaps)))] if gaps else None
