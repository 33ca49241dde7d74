"""Kernels: the splash attention kernel's share of its roofline. The least
time the chip could take for the calls SEEN IN THE TRACE (operations and
bytes per call from the shapes, ``counts/splash.py``; the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the kernel's
summed device time. In %. Source: the trainer's on-demand profile bundle."""

from benchmark.counts import peaks, splash


def kernel_calls(ops: dict) -> dict[str, dict]:
    """Forward and backward splash kernels among the trace's operations."""
    found = {"forward": {"self_s": 0.0, "calls": 0},
             "backward": {"self_s": 0.0, "calls": 0}}
    for name, op in ops.items():
        low = name.lower()
        if "splash" not in low:
            continue
        kind = ("backward" if any(t in low for t in ("bwd", "dkv", "dq",
                                                     "backward"))
                else "forward")
        found[kind]["self_s"] += op["self_s"]
        found[kind]["calls"] += op["calls"]
    return found


def read(run: dict):
    trace = run.get("trace")
    if not trace or run["device"]["platform"] != "tpu":
        return None
    found = kernel_calls(trace["ops"])
    took = sum(k["self_s"] for k in found.values())
    if took <= 0:
        return None
    cfg, job = run["config"], run["job"]
    shape = (job["global_batch"], cfg["n_head"], job["seq"],
             cfg["n_embd"] // cfg["n_head"])
    peak = peaks.peaks(run["device"]["kind"])
    least = (found["forward"]["calls"] * splash.least_seconds(
                 splash.forward_call(*shape), peak)[0]
             + found["backward"]["calls"] * splash.least_seconds(
                 splash.backward_call(*shape), peak)[0])
    return 100.0 * least / took
