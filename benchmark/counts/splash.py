"""Operations and bytes one call of the splash attention kernel needs.

Causal attention over ``[batch, heads, seq, head_dim]`` in bf16. Needed
means the causal triangle, seq * (seq + 1) / 2 query-key pairs, although
the kernel computes whole diagonal blocks: work the algorithm does not
need does not raise a roofline share. The forward is two matrix products
per pair (QK^T, PV); the fused backward five (QK^T again, dP = dO V^T,
dV = P^T dO, dQ = dS K, dK = dS^T Q). Bytes are each operand and result
once: forward reads q, k, v and writes o and the f32 log-sum-exp;
backward reads q, k, v, o, dO and the log-sum-exp and writes dq, dk, dv.
"""

BF16 = 2
F32 = 4


def _pairs(seq: int) -> float:
    return seq * (seq + 1) / 2


def forward_call(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    tensor = batch * heads * seq * head_dim
    return {
        "flops": 2 * 2 * batch * heads * _pairs(seq) * head_dim,
        "bytes": 4 * tensor * BF16 + batch * heads * seq * F32,
    }


def backward_call(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    tensor = batch * heads * seq * head_dim
    return {
        "flops": 5 * 2 * batch * heads * _pairs(seq) * head_dim,
        "bytes": 8 * tensor * BF16 + batch * heads * seq * F32,
    }


def least_seconds(call: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``call`` and which bound
    sets it: operations over peak FLOP/s, or bytes over peak bytes/s."""
    by_compute = call["flops"] / peak["bf16_flops"]
    by_memory = call["bytes"] / peak["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))
