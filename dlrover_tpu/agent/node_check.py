"""Node-health probe payload: matmul + collective over the probe group.

Reference analog: dlrover/trainer/torch/node_check/nvidia_gpu.py (:26) and
utils.py (bm_all_gather, matmul, mock_error via MOCK_ERR_RANK). On TPU the
probe is a jitted bf16 matmul (MXU exercise) plus, when a multi-node probe
group exists, a psum over the group (ICI/DCN exercise). Runs in a
subprocess so a wedged chip cannot hang the agent.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from dlrover_tpu.common import envspec
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# import-time read by design (envspec: restart_required) — the probe
# budget must be identical across every probe child of one agent
PROBE_TIMEOUT_S = envspec.get_float(EnvKey.PROBE_TIMEOUT)
GLOBAL_RANK_ENV = EnvKey.GLOBAL_RANK


def _probe_payload() -> float:
    """The in-process probe; returns elapsed seconds. Exits nonzero on fault."""
    mock_rank = os.environ.get(EnvKey.MOCK_ERR_RANK)
    # fault injection keys on the node's GLOBAL rendezvous rank — probe
    # groups renumber ranks within each pair, and the mock must follow the
    # node, not its position in a pair
    node_rank = int(os.environ.get(EnvKey.NODE_RANK, "0"))
    global_rank = int(os.environ.get(GLOBAL_RANK_ENV, str(node_rank)))
    if mock_rank is not None and int(mock_rank) == global_rank:
        raise RuntimeError("mock error injected by MOCK_ERR_RANK")

    import jax
    import jax.numpy as jnp

    num_nodes = int(os.environ.get(EnvKey.NODE_NUM, "1"))
    coordinator = os.environ.get(EnvKey.COORDINATOR, "")
    if num_nodes > 1 and coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_nodes,
            process_id=node_rank,
        )

    start = time.monotonic()
    size = 2048
    x = jnp.ones((size, size), dtype=jnp.bfloat16)

    @jax.jit
    def matmul_chain(a):
        for _ in range(8):
            a = a @ a / size
        return a

    y = matmul_chain(x)
    y.block_until_ready()
    # compute-only time: this is the straggler signal — the collective
    # below gates on the slowest group member, so its wall clock cannot
    # distinguish a slow chip from a slow partner
    local_elapsed = time.monotonic() - start

    if num_nodes > 1:
        # 16M-element allreduce across every device in the probe group
        # (reference probe size: bm_all_gather's 16M elements).
        per_dev = 16 * 1024 * 1024 // max(1, jax.device_count())
        data = jnp.ones((jax.local_device_count(), per_dev), jnp.float32)
        reduced = jax.pmap(lambda v: jax.lax.psum(v, "probe"),
                           axis_name="probe")(data)
        reduced.block_until_ready()
    return time.monotonic() - start, local_elapsed


def run_node_check(node_rank: int, num_nodes: int, coordinator: str,
                   global_rank: int | None = None
                   ) -> tuple[float, bool, float]:
    """Run the probe in a subprocess.

    Returns (elapsed_s, succeeded, local_elapsed_s) — the last being the
    compute-only portion used for straggler detection.
    """
    env = dict(os.environ)
    env[EnvKey.NODE_RANK] = str(node_rank)
    env[EnvKey.NODE_NUM] = str(num_nodes)
    env[EnvKey.COORDINATOR] = coordinator
    env[GLOBAL_RANK_ENV] = str(
        global_rank if global_rank is not None else node_rank
    )
    start = time.monotonic()
    try:
        out = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.agent.node_check"],
            env=env, timeout=PROBE_TIMEOUT_S, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        logger.error("node check timed out after %ss", PROBE_TIMEOUT_S)
        return PROBE_TIMEOUT_S, False, 0.0
    if out.returncode != 0:
        logger.error("node check failed: %s", out.stderr[-2000:])
        return time.monotonic() - start, False, 0.0
    try:
        result = json.loads(out.stdout.strip().splitlines()[-1])
        elapsed = result["elapsed"]
        local = result.get("local", 0.0)
    except (json.JSONDecodeError, IndexError, KeyError):
        elapsed, local = time.monotonic() - start, 0.0
    return elapsed, True, local


def main() -> int:
    elapsed, local = _probe_payload()
    print(json.dumps({"elapsed": elapsed, "local": local}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
