"""Trainer-process bring-up from the agent-provided environment.

Reference analog: torchelastic workers read RANK/WORLD_SIZE/MASTER_ADDR set
by the agent (dlrover/python/elastic_agent/torch/training.py worker env
assembly). TPU-natively the agent hands the JAX coordination service address
from the completed rendezvous and the trainer calls
``jax.distributed.initialize`` — after that every process sees the global
device set and a single ``Mesh`` spans hosts.
"""

from __future__ import annotations

import dataclasses
import os

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)


def exit_oom() -> None:
    """Report out-of-memory to the agent via the exit-code contract."""
    from dlrover_tpu.agent.failure_policy import EXIT_CODE_OOM

    os._exit(EXIT_CODE_OOM)


def exit_hardware_fault() -> None:
    """Report an unrecoverable chip/host fault: the agent escalates to node
    relaunch instead of restarting in place."""
    from dlrover_tpu.agent.failure_policy import EXIT_CODE_HARDWARE

    os._exit(EXIT_CODE_HARDWARE)


class failure_contract:
    """Context manager translating runtime faults to the exit-code contract.

    Wrap the training loop::

        with bootstrap.failure_contract():
            trainer.run(...)

    XLA RESOURCE_EXHAUSTED (HBM/host OOM) exits 210 so the agent reports
    OOM to the master's resource optimizer; everything else propagates and
    becomes a software error.
    """

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is None:
            return False
        text = f"{exc_type.__name__}: {exc}"
        if "RESOURCE_EXHAUSTED" in text or isinstance(exc, MemoryError):
            logger.error("out of memory: %s", text[:2000])
            exit_oom()
        return False


@dataclasses.dataclass
class RunContext:
    job_name: str = "local"
    node_id: int = 0
    node_rank: int = 0
    num_nodes: int = 1
    restart_count: int = 0
    coordinator: str = ""
    master_addr: str = ""
    under_agent: bool = False


def _cpu_only() -> bool:
    """JAX is held to the CPU (``JAX_PLATFORMS=cpu``, as the tests run):
    read from JAX's own config, which does not initialize a backend."""
    import jax

    return (jax.config.jax_platforms or "").lower() == "cpu"


def setup_compilation_cache() -> str | None:
    """Turn on XLA's persistent compilation cache; returns its directory
    (None on the CPU, where it stays off).

    The goodput lever for elasticity x static compilation (SURVEY §7 hard
    parts): every restart-in-place re-traces the same program, and without
    this cache each incarnation pays the full XLA compile (tens of seconds
    to minutes at scale) before its first step. With it, a restarted
    process deserializes the executable in ~1s, so the per-failure cost is
    rendezvous + restore, not recompilation. The reference has no analog —
    torch re-executes eagerly — this cost class only exists under XLA, and
    this is its native fix.

    Placement is ``parallel/compile_cache.cache_root``: where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself; nothing is
    set here), else one fixed directory in the checkout that every
    incarnation, the parked standby and the serving replicas share. On
    the CPU (tests run with ``JAX_PLATFORMS=cpu``) the cache is off:
    XLA:CPU reloads cached executables compiled for other machine
    features and misexecutes them.
    """
    import jax

    from dlrover_tpu.parallel.compile_cache import cache_root

    if _cpu_only():
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    cache_dir = cache_root()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache even fast compiles: restart storms re-pay them N times
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def describe_devices() -> str:
    """One line naming the device a result came from, as JAX reports it
    (initializes the backend): every entry point prints it, so a log
    never leaves open whether a run touched the chip."""
    import jax

    devices = jax.devices()
    return (f"devices: platform={devices[0].platform} "
            f"kind={devices[0].device_kind!r} count={len(devices)}")


def init_from_env(initialize_distributed: bool = True) -> RunContext:
    """Read the agent contract from env; multi-node: join the JAX cluster.

    Safe to call without an agent (standalone notebooks/benchmarks): returns
    a single-node context and skips ``jax.distributed.initialize``.

    Nothing here chooses the JAX platform: tests run with
    ``JAX_PLATFORMS=cpu`` in the environment, and on the chip nothing is
    forced.
    """
    setup_compilation_cache()
    if os.environ.get(EnvKey.MASTER_ADDR):
        # arm the flight recorder's C-level SIGUSR2 stack dump
        # (telemetry/bundle.py): faulthandler dumps without the GIL, so
        # the agent can read this process's stacks even when it is
        # wedged inside a collective — the evidence a hang verdict's
        # debug bundle scoops up before the kill
        from dlrover_tpu.telemetry.bundle import arm_child_dump

        arm_child_dump()
    if os.environ.get(EnvKey.STANDBY_FILE):
        # warm-standby trainer (agent/standby.py): everything above —
        # interpreter + import graph, platform config, compile cache,
        # flight recorder — is pre-paid; park here until the agent
        # promotes this process with the rendezvous payload. The
        # accelerator backend and jax.distributed.initialize must wait
        # for promotion (chips are exclusive to the live trainer, and
        # the coordinator address only exists after rendezvous).
        from dlrover_tpu.agent.standby import park_if_standby

        park_if_standby()
    ctx = RunContext(
        job_name=os.environ.get(EnvKey.JOB_NAME, "local"),
        node_id=int(os.environ.get(EnvKey.NODE_ID, "0")),
        node_rank=int(os.environ.get(EnvKey.NODE_RANK, "0")),
        num_nodes=int(os.environ.get(EnvKey.NODE_NUM, "1")),
        restart_count=int(os.environ.get(EnvKey.RESTART_COUNT, "0")),
        coordinator=os.environ.get(EnvKey.COORDINATOR, ""),
        master_addr=os.environ.get(EnvKey.MASTER_ADDR, ""),
        under_agent=bool(os.environ.get(EnvKey.MASTER_ADDR)),
    )
    if initialize_distributed and ctx.num_nodes > 1 and ctx.coordinator:
        import jax

        logger.info(
            "joining jax cluster: rank %d/%d coordinator %s (restart %d)",
            ctx.node_rank, ctx.num_nodes, ctx.coordinator, ctx.restart_count,
        )
        init_kwargs = {}
        init_timeout = os.environ.get(EnvKey.INIT_TIMEOUT, "")
        if init_timeout:
            # launcher-scaled join timeout (run.py auto_configure): a
            # large fleet's restart storm outlives the 300 s default
            init_kwargs["initialization_timeout"] = int(float(init_timeout))
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator,
            num_processes=ctx.num_nodes,
            process_id=ctx.node_rank,
            **init_kwargs,
        )
    return ctx
