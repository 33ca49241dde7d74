"""GPT-2 in plain ``jax.numpy``: the benchmark's yardstick for `correct`.

Written from the published description (Radford et al. 2019; the
openai-community ``config.json`` keys ``n_embd``, ``n_layer``, ``n_head``,
``n_positions``, ``vocab_size``, ``layer_norm_epsilon`` 1e-5,
``activation_function`` gelu_new): learned position embeddings, pre-norm
blocks with LayerNorm (scale and bias), causal multi-head attention with
1/sqrt(head_dim) scaling, a 4x GELU (tanh form) feed-forward with biases,
a final LayerNorm, next-token cross entropy. It imports nothing of the
program. float32, every product at ``Precision.HIGHEST``; no kernel, no
cache, no batching tricks.

Departures from the publication, because the system under test serves
this layout and the reference must compute the same function:
  * the output head is a matrix of its own (``lm_head``), not the
    transposed token embedding;
  * the attention projections carry no bias;
  * seeded weights are normal / sqrt(fan_in) (positions 0.01 * normal),
    not the publication's 0.02: random weights stand in for a checkpoint.
The parameter tree is the system's checkpoint layout (layer-stacked):
``embed [V,E]``, ``pos_embed [P,E]``, ``layers.{wq,wk,wv [L,E,H,D], wo
[L,H,D,E], ln1, ln1_b, ln2, ln2_b [L,E], w_gate [L,E,F], b_ff [L,F],
w_down [L,F,E], b_out [L,E]}``, ``ln_f``, ``ln_f_b [E]``, ``lm_head [E,V]``.

``precision`` selects the arithmetic of the matrix products only:
``"f32"`` is the reference; ``"fp8"`` rounds both operands of every
product to e4m3 and accumulates in float32 — the control that `correct`
must reject (the precision below the bfloat16 the configurations state).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROUND_TO = {"f32": None, "fp8": jnp.float8_e4m3fn}


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    e, h = cfg["n_embd"], cfg["n_head"]
    f = cfg.get("n_inner") or 4 * e
    return (cfg["vocab_size"], cfg["n_positions"], cfg["n_layer"], e, h,
            e // h, f)


@partial(jax.jit, static_argnums=(0,))
def _init(shape_key: tuple, key: jax.Array) -> dict:
    v, p, n, e, h, d, f = shape_key
    ks = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    ones = partial(jnp.ones, dtype=jnp.float32)
    zeros = partial(jnp.zeros, dtype=jnp.float32)
    layers = {
        "wq": normal(ks[0], (n, e, h, d), e),
        "wk": normal(ks[1], (n, e, h, d), e),
        "wv": normal(ks[2], (n, e, h, d), e),
        "wo": normal(ks[3], (n, h, d, e), e),
        "w_gate": normal(ks[4], (n, e, f), e),
        "w_down": normal(ks[5], (n, f, e), f),
        "ln1": ones((n, e)), "ln1_b": zeros((n, e)),
        "ln2": ones((n, e)), "ln2_b": zeros((n, e)),
        "b_ff": zeros((n, f)), "b_out": zeros((n, e)),
    }
    return {
        "embed": normal(ks[6], (v, e), e),
        "pos_embed": 0.01 * jax.random.normal(ks[7], (p, e), jnp.float32),
        "layers": layers,
        "ln_f": ones((e,)), "ln_f_b": zeros((e,)),
        "lm_head": normal(ks[8], (e, v), e),
    }


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded float32 weights, made on the device in one jitted call."""
    return _init(dims(cfg), key_for(seed))


def _product(expr: str, a, b, precision: str):
    to = ROUND_TO[precision]
    if to is None:
        return jnp.einsum(expr, a, b, precision=jax.lax.Precision.HIGHEST)
    # rounded on the way forward only: the backward pass sees the float32
    # operand (straight through), as scaled low-precision training keeps
    # its gradients out of the narrow type's underflow
    a = a + jax.lax.stop_gradient(a.astype(to).astype(jnp.float32) - a)
    b = b + jax.lax.stop_gradient(b.astype(to).astype(jnp.float32) - b)
    return jnp.einsum(expr, a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, eps, precision):
    """One pre-norm block on ``x [B,S,E]``."""
    mm = partial(_product, precision=precision)
    s = x.shape[1]
    h = _layer_norm(x, w["ln1"], w["ln1_b"], eps)
    q = mm("bse,ehd->bshd", h, w["wq"])
    k = mm("bse,ehd->bshd", h, w["wk"])
    v = mm("bse,ehd->bshd", h, w["wv"])
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v)
    x = x + mm("bshd,hde->bse", o, w["wo"])
    h = _layer_norm(x, w["ln2"], w["ln2_b"], eps)
    hidden = _gelu_new(mm("bse,ef->bsf", h, w["w_gate"]) + w["b_ff"])
    return x + mm("bsf,fe->bse", hidden, w["w_down"]) + w["b_out"]


def hidden_states(params: dict, tokens, eps: float = 1e-5,
                  precision: str = "f32", remat: bool = False):
    """Token ids ``[B,S]`` -> final-normed hidden states ``[B,S,E]``."""
    x = params["embed"][tokens] + params["pos_embed"][: tokens.shape[1]]
    block = partial(_block, eps=eps, precision=precision)
    if remat:  # layer by layer, so that a training batch fits the chip
        block = jax.checkpoint(block)

    def body(x, w):
        return block(x, w), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _layer_norm(x, params["ln_f"], params["ln_f_b"], eps)


def logits(params: dict, tokens, eps: float = 1e-5,
           precision: str = "f32"):
    """Token ids ``[B,S]`` -> float32 logits ``[B,S,V]``."""
    hid = hidden_states(params, tokens, eps, precision)
    return _product("bse,ev->bsv", hid, params["lm_head"], precision)


def loss(params: dict, tokens, eps: float = 1e-5, precision: str = "f32",
         remat: bool = True):
    """Mean next-token cross entropy of ``tokens [B,S+1]``."""
    hid = hidden_states(params, tokens[:, :-1], eps, precision, remat)
    lg = _product("bse,ev->bsv", hid, params["lm_head"], precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(params: dict, tokens, rows: int = 2,
                   precision: str = "f32"):
    """Loss and gradients of the whole batch, in blocks of ``rows`` rows
    (equal blocks: the batch mean is the mean of the block means)."""
    fn = jax.jit(jax.value_and_grad(partial(loss, precision=precision)))
    n = tokens.shape[0]
    if n % rows:
        raise ValueError(f"{n} rows do not split into blocks of {rows}")
    total, grads = 0.0, None
    for lo in range(0, n, rows):
        value, g = fn(params, tokens[lo: lo + rows])
        total += float(value)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    blocks = n // rows
    return total / blocks, jax.tree.map(lambda a: a / blocks, grads)


def leaf_norms(tree) -> dict[str, float]:
    """L2 norm of every leaf, by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(
        jnp.asarray(leaf, jnp.float32).ravel())) for path, leaf in flat}


def norm_gap(program: dict[str, float], reference: dict[str, float]) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(reference.values())))
    return max(abs(program[k] - reference[k]) / max(reference[k], median)
               for k in reference)
