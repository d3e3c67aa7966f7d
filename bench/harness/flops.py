"""Operations and bytes the measured work needs, counted from shapes.

``tt_chain_cost`` follows the left-to-right order of a lead-absorbed TT
chain (``cores[0]`` is ``(n_1, r_1)``, later cores ``(r, n, s)``, the first
``split`` cores are input cores), which is the order the fused
``tt_contract`` kernels and their oracle use.  Bytes are what one call
must move: the activations in, every core once, the result out, at the
widths the kernel reads and writes them (f32 throughout).

``decode_flops_per_token`` counts the matrix work of one token through the
TT-form transformer: the TT chains of every layer (per row, so the lead
absorption, done once per layer per step, is left out), attention over the
token's live context (QK and PV), and the unembedding.  Norms, rope and
the softmax are left out: the count is a lower bound, so a utilization
built on it can only read low.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Sequence


class Cost(NamedTuple):
    flops: float
    bytes: float


def _prod(xs) -> int:
    return int(math.prod(xs))


def tt_chain_cost(batch: int, core_shapes: Sequence[Sequence[int]],
                  split: int, itemsize: int = 4) -> Cost:
    """One contraction ``(batch, N_in) -> (batch, N_out)`` through the
    chain.  ``core_shapes[0]`` is ``(n_1, r_1)``; the rest ``(r, n, s)``."""
    g0 = core_shapes[0]
    n_in = g0[0] * _prod(c[1] for c in core_shapes[1:split])
    n_out = _prod(c[1] for c in core_shapes[split:])
    flops = 2.0 * batch * n_in * g0[1]
    rest = n_in // g0[0]                 # input modes not yet consumed
    for r, n, s in core_shapes[1:split]:
        rest //= n
        flops += 2.0 * batch * n * rest * r * s
    m = 1                                # output modes built so far
    for r, n, s in core_shapes[split:]:
        flops += 2.0 * batch * m * r * n * s
        m *= n
    core_elems = sum(_prod(c) for c in core_shapes)
    nbytes = itemsize * (batch * n_in + core_elems + batch * n_out)
    return Cost(flops, float(nbytes))


def least_time(cost: Cost, peak_flops: float, peak_bytes: float):
    """(seconds, bound): the larger of compute time and memory time."""
    tc, tm = cost.flops / peak_flops, cost.bytes / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


def attention_flops(context: float, num_heads: int, head_dim: int) -> float:
    """QK^T and PV for one query token over ``context`` keys, one layer."""
    return 4.0 * num_heads * head_dim * context


def decode_flops_per_token(chains, num_layers: int, num_heads: int,
                           head_dim: int, d_model: int, vocab: int,
                           context: float) -> float:
    """``chains``: per-layer TT leaves as ``(core_shapes, split)`` with the
    lead already absorbed; ``context``: mean live keys per stepped token."""
    per_layer = sum(tt_chain_cost(1, shapes, split).flops
                    for shapes, split in chains)
    per_layer += attention_flops(context, num_heads, head_dim)
    return num_layers * per_layer + 2.0 * d_model * vocab


def mean_context(lengths: Sequence[int]) -> float:
    """Mean live keys per stepped token over requests of total length
    ``L = prompt + answer``: a request steps ``L - 1`` tokens and position
    ``p`` attends to ``p + 1`` keys."""
    steps = sum(max(n - 1, 0) for n in lengths)
    keys = sum((n - 1) * n / 2 for n in lengths if n > 1)
    return keys / steps if steps else 0.0
